"""Whole runs of the tiny cell on the CPU, the chip check skipped: a sound
run is correct, and each fault planted in the timed path underneath makes
``correct`` false.  Also: without a TPU the command exits non-zero and
prints no result, and so it does with only the benchmark's own files."""
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import runner
from bench.harness.registry import Registry
from repro.serving import engine as engine_mod

from bench.tests import tiny

ROOT = tiny.BENCH.parent


@pytest.fixture
def bench_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return tiny.make(tmp_path)


def _run(d, seed=11, **kw):
    return runner.run_cell(Registry(d), "tiny.mix", seed, 2.0, False,
                           time.perf_counter(), need_chip=False, **kw)


def test_sound_run_is_correct(bench_dir):
    res = _run(bench_dir)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p90_ms", "itl_p99_ms",
                                   "setup_s"}
    assert res["checks"]["logit_gap"]["value"] <= 1e-3


def _alter_tokens(monkeypatch):
    emit = engine_mod.Engine._emit

    def altered(self, reqs, out, rows, emitted, slots=None):
        emit(self, reqs, out, rows, emitted, slots)
        for req in reqs:
            tok = (req.tokens[-1] + 1) % self.model.cfg.vocab
            req.tokens[-1] = tok
            emitted[req.rid][-1] = tok

    monkeypatch.setattr(engine_mod.Engine, "_emit", altered)


def _drop_state(monkeypatch):
    step = engine_mod.Engine.step

    def forgetful(self):
        out = step(self)
        self.pool.tree = jax.tree.map(jnp.zeros_like, self.pool.tree)
        return out

    monkeypatch.setattr(engine_mod.Engine, "step", forgetful)


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_state],
                         ids=["token_altered", "kv_state_lost"])
def test_fault_makes_it_incorrect(bench_dir, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(bench_dir)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > 1e-3


def _corrupt_some_requests(monkeypatch):
    """The K/V pages of every third request are overwritten with ones after
    every step, as writes for a few batch rows gone wrong."""
    step = engine_mod.Engine.step

    def faulty(self):
        out = step(self)
        pages = [p for r in self.sched.running if r.rid % 3 == 0 for p in r.pages]
        if pages:
            idx = jnp.asarray(pages, jnp.int32)
            self.pool.tree = jax.tree.map(lambda x: x.at[idx].set(1),
                                          self.pool.tree)
        return out

    monkeypatch.setattr(engine_mod.Engine, "step", faulty)


def test_fault_in_a_few_requests_makes_it_incorrect(tmp_path, monkeypatch):
    """Every finished request is compared here, so the faulted ones are
    among them; their gaps fail the cell's limit, the others' do not."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    cell = dict(tiny.CELL, correct=dict(tiny.CELL["correct"], min_requests=64,
                                        max_requests=64))
    d = tiny.make(tmp_path, cell)
    _corrupt_some_requests(monkeypatch)
    res = _run(d)
    assert res["correct"] is False
    assert 0 < res["failed"] < res["attempted"]
    assert res["checks"]["logit_gap"]["value"] > cell["correct"]["limit"]


def test_control_is_not_correct(bench_dir):
    """The control's tokens, judged by the harness's own verdict at the
    cell's limit, come out not correct; the program's on the same sample
    are correct."""
    res = _run(bench_dir, control=True)
    limit = tiny.CELL["correct"]["limit"]
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > limit
    assert res["program"]["correct"] is True
    assert res["program"]["logit_gap"] <= limit


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "qwen2-1.5b.chat.exact",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _command(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _command(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
