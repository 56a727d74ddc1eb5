"""Traffic harness + desynchronized drain + scheduler fairness (ISSUE 10).

Three contracts:

* workload/harness determinism — ``generate_arrivals`` is a pure function
  of its config, and replaying one trace against two fresh engines gives
  identical token streams (the property the CI parity lanes lean on);

* the desynchronized stats drain (``ServingConfig.drain_interval``) —
  token streams never change (the fused kernels repair on read with a
  value-independent fill), ``drain_interval=1`` replays the lockstep
  engine's scrub trajectory bit-for-bit (same final pool bits, same
  unified stats, same per-page ledger), and every desync point issues
  STRICTLY fewer blocking host syncs;

* scheduler fairness under load — chunked prefill must not starve a
  decoding request (vllm-style mixed batching), and a preemption storm
  must resolve FIFO-fair: the oldest request is never evicted and every
  victim still finishes.
"""
import dataclasses

import jax
import numpy as np
import pytest
from conftest import tiny_transformer

from repro.serving import (
    Engine, ServingConfig, WorkloadConfig, generate_arrivals,
)


@pytest.fixture(scope="module")
def model_params():
    return tiny_transformer()


# ------------------------------------------------------------- workload
def test_workload_regenerates_bit_equal():
    cfg = WorkloadConfig(
        n_requests=12, arrival_rate=0.6, prompt_len=(2, 6),
        long_prompt_len=(8, 12), long_frac=0.4, output_len=(2, 5), seed=3,
    )
    a = generate_arrivals(cfg)
    b = generate_arrivals(cfg)
    assert [(x.step, x.prompt, x.max_new) for x in a] == [
        (x.step, x.prompt, x.max_new) for x in b
    ]
    assert all(a[i].step <= a[i + 1].step for i in range(len(a) - 1))
    # a different seed is a different trace
    c = generate_arrivals(dataclasses.replace(cfg, seed=4))
    assert [(x.step, x.prompt) for x in a] != [(x.step, x.prompt) for x in c]


def test_workload_burst_lands_on_one_step():
    cfg = WorkloadConfig(
        n_requests=4, arrival_rate=0.5, prompt_len=(2, 4),
        output_len=(2, 3), burst_at=2, burst_n=5, seed=9,
    )
    arrivals = generate_arrivals(cfg)
    assert len(arrivals) == 9
    assert sum(1 for a in arrivals if a.step == 2) >= 5


def test_workload_validation():
    with pytest.raises(ValueError):
        WorkloadConfig(arrival_rate=0.0)
    with pytest.raises(ValueError):
        WorkloadConfig(prompt_len=(5, 2))
    with pytest.raises(ValueError):
        WorkloadConfig(long_frac=1.5)
    with pytest.raises(ValueError):
        ServingConfig(drain_interval=-1)


# -------------------------------------------------------------- harness
def _cfg(**kw) -> ServingConfig:
    base = dict(
        page_size=4, n_pages=10, max_batch=4, max_pages_per_request=4,
        prefill_chunk=4, ber=0.0, seed=7,
    )
    base.update(kw)
    return ServingConfig(**base)


def test_harness_seed_deterministic(model_params):
    """The CI `traffic` lane's single-device half: same seed + same config
    => same arrivals => same token streams from two fresh engines."""
    from benchmarks.traffic import drive

    model, params = model_params
    wl = WorkloadConfig(
        n_requests=5, arrival_rate=0.8, prompt_len=(2, 5),
        long_prompt_len=(6, 9), long_frac=0.3, output_len=(2, 4), seed=13,
    )
    rep_a = drive(Engine(model, params, _cfg()), generate_arrivals(wl))
    rep_b = drive(Engine(model, params, _cfg()), generate_arrivals(wl))
    assert rep_a["token_streams"] == rep_b["token_streams"]
    assert rep_a["tokens_emitted"] == rep_b["tokens_emitted"] > 0
    assert rep_a["n_requests"] == 5
    for key in (
        "p50_ms_per_token", "p99_ms_per_token", "ttft_p50_ms",
        "tokens_per_s", "scrubbed_bytes_per_token", "n_host_syncs",
    ):
        assert key in rep_a, key


# ------------------------------------------------- desynchronized drain
def _pool_bits(engine: Engine):
    return [
        np.asarray(leaf, np.float32).view(np.uint32)
        for leaf in jax.tree.leaves(engine.pool.tree)
    ]


def _one_request_pair(model, params, drain_interval):
    """Two engines, one request each, identical flips — prefill and decode
    never share a step, so drain_interval=1 replays the lockstep scrub
    trajectory exactly."""
    out = []
    for di in (0, drain_interval):
        eng = Engine(
            model, params,
            _cfg(ber=2e-3, prefill_chunk=0, drain_interval=di, n_pages=7),
        )
        assert eng._paged_fn is not None and eng._prefill_fn is not None
        eng.add_request([5, 9, 2, 14, 3, 7], max_new=8)
        eng.run()
        out.append(eng)
    return out


def test_desync_interval1_bit_replays_lockstep(model_params):
    model, params = model_params
    lock, desync = _one_request_pair(model, params, drain_interval=1)
    assert lock._desync is False and desync._desync is True
    # the run actually exercised repair (the test has teeth)
    assert lock.stats_dict()["events"] > 0
    assert desync.results[0]["tokens"] == lock.results[0]["tokens"]
    # identical scrub trajectory: unified stats, kernel totals, per-page
    # ledger, and the final pool bits all replay
    assert desync.stats_dict() == lock.stats_dict()
    np.testing.assert_array_equal(desync.kernel_counts, lock.kernel_counts)
    np.testing.assert_array_equal(
        desync.pool.page_events, lock.pool.page_events
    )
    for a, b in zip(_pool_bits(desync), _pool_bits(lock)):
        np.testing.assert_array_equal(a, b)
    # and the whole point: strictly fewer blocking device->host readbacks
    assert desync.n_host_syncs < lock.n_host_syncs


def test_desync_wide_interval_token_parity_under_load(model_params):
    """drain_interval=3 under mixed chunked-prefill + decode traffic: the
    scrub happens steps later, but the kernels repair on read — tokens and
    throughput accounting must not move, syncs must drop further."""
    from benchmarks.traffic import drive

    model, params = model_params
    wl = WorkloadConfig(
        n_requests=5, arrival_rate=0.9, prompt_len=(2, 5),
        long_prompt_len=(6, 10), long_frac=0.4, output_len=(2, 5), seed=21,
    )
    reps = {}
    for di in (0, 1, 3):
        eng = Engine(model, params, _cfg(ber=1e-3, drain_interval=di))
        reps[di] = drive(eng, generate_arrivals(wl))
    assert reps[1]["token_streams"] == reps[0]["token_streams"]
    assert reps[3]["token_streams"] == reps[0]["token_streams"]
    assert reps[0]["tokens_emitted"] > 0
    assert reps[1]["n_host_syncs"] < reps[0]["n_host_syncs"]
    assert reps[3]["n_host_syncs"] < reps[1]["n_host_syncs"]


def _host_spans(trace_dir):
    """``(name, start_ns, end_ns)`` of every event on the host thread that
    ran the engine (the one holding its ``engine.step`` spans)."""
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            if any(n == "engine.step" for n, _, _ in evs):
                return evs
    raise AssertionError("no engine.step span in the profile")


def test_metrics_expose_syncs_and_stage_walls(model_params, tmp_path):
    """Every stage of a step is a profiler span on the profiler's clock,
    nested in its ``engine.step``; each blocking readback is one
    ``engine.readback`` (so the spans and ``n_host_syncs`` agree) and each
    prompt chunk dispatched one ``engine.prefill_chunk``."""
    from repro.runtime.config import AutopilotConfig

    model, params = model_params
    guard = AutopilotConfig(window=2, tolerance=1.0, floor=0.0, patience=1,
                            cooldown=0, expected=(("default", 0.0),))
    # fused lockstep; fused with a deferred drain and the guard; gathered
    fused = Engine(model, params, _cfg())
    desync = Engine(model, params, _cfg(drain_interval=2, autopilot=guard))
    gathered = Engine(model, params, _cfg(paged_decode="off"))
    fused.add_request(list(range(1, 11)), max_new=3)     # chunks 4, 4, 2
    fused.add_request([4, 8, 15], max_new=3)             # one chunk
    desync.add_request([5, 6, 7, 8, 9], max_new=4)       # chunks 4, 1
    gathered.add_request([4, 8, 15], max_new=3)          # one whole prompt
    engines = (fused, desync, gathered)
    assert fused._prefill_fn is not None and desync._desync
    assert gathered._paged_fn is None and desync.guard is not None
    syncs0 = [e.n_host_syncs for e in engines]
    jax.profiler.start_trace(str(tmp_path))
    n_steps = 0
    for eng in engines:
        while eng.has_work:
            eng.step()
            n_steps += 1
    jax.profiler.stop_trace()
    syncs = sum(e.n_host_syncs - s for e, s in zip(engines, syncs0))

    spans = _host_spans(str(tmp_path))
    names = {n for n, _, _ in spans}
    assert {"engine.step", "engine.drain", "engine.admit", "engine.prefill",
            "engine.prefill_chunk", "engine.decode", "engine.repair",
            "engine.sweep", "engine.guard", "engine.readback",
            "pool.reset_pages"} <= names
    steps = [(a, b) for n, a, b in spans if n == "engine.step"]
    assert len(steps) == n_steps
    for name, a, b in spans:
        if name.startswith("engine.") and name != "engine.step":
            assert any(s <= a and b <= e for s, e in steps), name
    readbacks = [s for s in spans if s[0] == "engine.readback"]
    assert syncs > 0 and len(readbacks) == syncs
    chunks = [s for s in spans if s[0] == "engine.prefill_chunk"]
    assert len(chunks) == 4 + 2 + 1
    # the engines' metrics carry the count the spans mirror, and no
    # host-clock stage timings
    m = fused.metrics()
    assert m["n_host_syncs"] > 0 and m["host_syncs_per_step"] > 0
    assert m["drain_interval"] == 0 and m["sharded_kernels"] is False
    assert not any("wall" in k for k in m)


# -------------------------------------------------- scheduler fairness
def test_chunked_prefill_does_not_starve_decode(model_params):
    """vllm-style mixed batching: while a long prompt streams 2-token
    chunks, the already-running request must emit exactly one decode token
    EVERY step — no decode starvation behind prefill."""
    model, params = model_params
    eng = Engine(
        model, params,
        _cfg(n_pages=8, max_batch=2, prefill_chunk=2),
    )
    assert eng._prefill_fn is not None
    rid_a = eng.add_request([3, 4], max_new=8)            # 1 chunk
    rid_b = eng.add_request(list(range(1, 13)), max_new=2)  # 6 chunks
    out0 = eng.step()
    # step 0: A finishes its prefill and emits; B streams its first chunk
    assert rid_a in out0["emitted"] and rid_b not in out0["emitted"]
    for t in range(1, 5):
        out = eng.step()
        assert out["emitted"].get(rid_a) is not None and len(
            out["emitted"][rid_a]
        ) == 1, f"decode starved at step {t}"
        assert rid_b not in out["emitted"]
        assert rid_b in {r.rid for r in eng._prefilling}
    out5 = eng.step()          # B's last chunk lands: both emit
    assert rid_b in out5["emitted"] and rid_a in out5["emitted"]
    res = eng.run()
    assert len(res[rid_a]["generated"]) == 8
    assert len(res[rid_b]["generated"]) == 2


def test_preemption_storm_stays_fifo_fair(model_params):
    """Page pressure must evict the NEWEST request, never the oldest, and
    every victim still finishes with its full output."""
    model, params = model_params
    eng = Engine(
        model, params,
        _cfg(page_size=4, n_pages=5, max_batch=2, prefill_chunk=0),
    )
    rid_old = eng.add_request([2, 3, 4, 5], max_new=12)   # grows to 4 pages
    rid_new = eng.add_request([6, 7, 8, 9], max_new=8)    # grows to 3 pages
    res = eng.run()
    assert eng.sched.n_preemptions > 0, "the storm must actually preempt"
    assert res[rid_old]["n_preempted"] == 0, "FIFO: the elder is never evicted"
    assert res[rid_new]["n_preempted"] > 0
    assert len(res[rid_old]["generated"]) == 12
    assert len(res[rid_new]["generated"]) == 8


def test_burst_workload_all_requests_complete(model_params):
    """A synchronized burst over a small pool: admission control + FIFO
    preemption must drain the whole trace — nobody starves."""
    from benchmarks.traffic import drive

    model, params = model_params
    wl = WorkloadConfig(
        n_requests=3, arrival_rate=0.8, prompt_len=(2, 5),
        long_prompt_len=(6, 10), long_frac=0.5, output_len=(2, 4),
        burst_at=1, burst_n=4, seed=17,
    )
    eng = Engine(model, params, _cfg(max_batch=2, n_pages=6))
    rep = drive(eng, generate_arrivals(wl))
    assert rep["n_requests"] == 7
    assert len(rep["token_streams"]) == 7
    assert all(len(s) > 0 for s in rep["token_streams"])
    # the oldest arrival is never a preemption victim
    assert eng.results[0]["n_preempted"] == 0
