"""The loop's own records: which requests an injection pass left with a
flip the detector passes, and the prompt chunks each engine step ran."""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loop, runner, traffic
from bench.harness.registry import Registry

from bench.tests import tiny

SHAPE = (5, 2, 4, 2, 8)          # pages, layers, rows, KV heads, lanes


def _engine(flip):
    """An engine stand-in: pages 0-1 held by request 7, page 2 by request
    8; ``flip`` sets one lane of page 1 in the injection pass."""
    base = jnp.asarray(np.random.default_rng(0).normal(size=SHAPE), jnp.bfloat16)
    tree = {"k": base, "v": base}

    def inject(tree, key, dose, donate=False):
        return dict(tree, k=tree["k"].at[1, 1, 2, 0, 3].set(flip)), None

    return types.SimpleNamespace(
        pool=types.SimpleNamespace(tree=tree),
        space=types.SimpleNamespace(inject=inject),
        sched=types.SimpleNamespace(running=[
            types.SimpleNamespace(rid=7, pages=[0, 1]),
            types.SimpleNamespace(rid=8, pages=[2])]))


@pytest.mark.parametrize("flip, tainted", [
    (300.0, {7}),                    # a passed lane, the largest of its head
    (3.0e9, {7}),                    # still under the 2**32 guard
    (0.0, set()),                    # no larger than what the page held
    (float("nan"), set()),           # detected: the engine repairs it
    (float("inf"), set()),
    (2.0 ** 33, set()),              # over the guard: detected
])
def test_inject_taints_requests_with_passed_outliers(flip, tainted):
    log = loop.Log(window=(0.0, 0.0))
    loop.inject(_engine(flip), None, 1e-9, log)
    assert log.tainted == tainted


def test_prefill_chunks_logged_per_step(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    reg = Registry(tiny.make(tmp_path))
    cell = runner.load_cell(reg, "tiny.mix")
    runner.import_system()
    engine = runner.build(cell, 3)
    arrivals = traffic.schedule(cell.traffic, 3, 0.0, 2.0, cell.config["vocab_size"])
    runner.warm(engine, cell, arrivals, 3)
    lg = loop.drive(engine, arrivals, lead_in_s=0.0, seconds=2.0)
    assert len(lg.chunks) == len(lg.steps)
    chunk = cell.engine["prefill_chunk"]
    chunks = [c for step in lg.chunks for c in step]
    assert all(0 < n <= chunk and q0 % chunk == 0 for q0, n, _ in chunks)
    # what the chunks add up to is what the engine prefilled: whole prompts
    # of the requests with a first token, the progress of the others
    done = [len(r["tokens"]) - len(r["generated"]) for r in engine.results.values()]
    done += [len(r.prompt) for r in engine.sched.running if r.tokens]
    partial = [r.prefill_pos or 0 for r in
               list(engine.sched.waiting) + engine.sched.running if not r.tokens]
    assert done
    assert sum(n for _, n, _ in chunks) == sum(done) + sum(partial)
    assert sorted(q0 + n for q0, n, last in chunks if last) == sorted(done)
