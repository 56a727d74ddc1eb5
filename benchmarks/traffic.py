"""Traffic bench: production-shaped load against the serving engine.

``repro.serving.workload`` generates a seed-deterministic arrival trace
(Poisson arrivals, bimodal prompt mix, optional burst); ``drive`` replays
it open-loop against a live engine — every arrival is submitted at its
trace step whether or not the engine has headroom, so admission control,
chunked prefill and preemption all run for a living — and reports the
latency distribution (p50/p99 wall-clock per token, time-to-first-token),
throughput, repair traffic per token, and the engine's host-sync count.

Four arms per run:

  traffic_ber0          the no-fault baseline
  traffic_ber0.001      the same trace under injected flips
  traffic_storm_ber0.001  a synchronized burst on top — the preemption
                        storm; asserted to actually preempt
  traffic_desync_ber0.001  the BER arm re-run with ``drain_interval=1``:
                        asserted token-identical to traffic_ber0.001 with
                        STRICTLY fewer blocking host syncs — the
                        desynchronized drain's whole claim, measured

Also asserted every run: regenerating the trace from the same seed gives
the identical arrival list, and driving a fresh engine with it gives the
identical token streams (the property the sharded-vs-single-device CI
parity lane leans on).  Wall-clock numbers are reported but not asserted:
off-TPU the Pallas kernels run in interpret mode.

``main(out=...)`` merges a ``traffic`` section into the shared bench
record (``benchmarks/run.py --out BENCH_repair.json``), validated by
``scripts/check_bench.py``.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.serving import (
    Engine, ServingConfig, WorkloadConfig, generate_arrivals,
)

from .serving_engine import _model


def drive(
    engine: Engine,
    arrivals: Sequence,
    max_idle_steps: int = 200,
) -> Dict[str, Any]:
    """Replay ``arrivals`` open-loop and collect the serving report.

    One harness tick = one engine step.  Arrivals whose trace step has
    come are submitted before the tick; the engine only actually steps
    while it has work (idle gaps between sparse arrivals fast-forward).
    Per-token latency samples charge each emitted token with its step's
    wall time; TTFT spans submission → first emitted token.
    """
    pending = deque(sorted(arrivals, key=lambda a: a.step))
    order: List[int] = []               # rids in submission order
    submit_wall: Dict[int, float] = {}
    ttft_s: Dict[int, float] = {}
    tok_lat_s: List[float] = []
    t0 = time.perf_counter()
    step_idx = 0
    idle = 0
    while pending or engine.has_work:
        while pending and pending[0].step <= step_idx:
            a = pending.popleft()
            rid = engine.add_request(list(a.prompt), a.max_new)
            order.append(rid)
            submit_wall[rid] = time.perf_counter()
        if engine.has_work:
            s0 = time.perf_counter()
            out = engine.step()
            s1 = time.perf_counter()
            for rid, toks in out["emitted"].items():
                if rid not in ttft_s:
                    ttft_s[rid] = s1 - submit_wall[rid]
                tok_lat_s.extend([s1 - s0] * len(toks))
            idle = 0 if (out["emitted"] or out["finished"]) else idle + 1
            if idle > max_idle_steps:
                raise RuntimeError(
                    f"engine made no progress in {max_idle_steps} steps"
                )
        step_idx += 1
    engine.drain()
    wall_s = time.perf_counter() - t0
    m = engine.metrics()
    toks = max(m["tokens_emitted"], 1)
    lat = np.asarray(tok_lat_s) if tok_lat_s else np.zeros(1)
    ttft = np.asarray(sorted(ttft_s.values())) if ttft_s else np.zeros(1)
    return {
        "token_streams": [engine.results[rid]["tokens"] for rid in order],
        "tokens_emitted": m["tokens_emitted"],
        "n_requests": len(order),
        "steps": step_idx,
        "wall_s": wall_s,
        "tokens_per_s": m["tokens_emitted"] / max(wall_s, 1e-9),
        "p50_ms_per_token": float(np.percentile(lat, 50) * 1e3),
        "p99_ms_per_token": float(np.percentile(lat, 99) * 1e3),
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
        "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
        "scrubbed_bytes_per_token": m["scrubbed_bytes_per_token"],
        "n_preemptions": m["n_preemptions"],
        "n_host_syncs": m["n_host_syncs"],
        "host_syncs_per_step": m["host_syncs_per_step"],
    }


def _serving_cfg(ber: float, drain_interval: int = 0) -> ServingConfig:
    return ServingConfig(
        page_size=4, n_pages=10, max_batch=4, max_pages_per_request=8,
        prefill_chunk=4, sweep_interval=16, sweep_pages=2,
        ber=ber, seed=7, drain_interval=drain_interval,
    )


def _workloads(smoke: bool) -> Dict[str, WorkloadConfig]:
    n = 8 if smoke else 20
    base = WorkloadConfig(
        n_requests=n, arrival_rate=0.8,
        prompt_len=(2, 6), long_prompt_len=(8, 14), long_frac=0.25,
        output_len=(2, 6) if smoke else (3, 10),
        vocab=97, seed=11,
    )
    import dataclasses

    storm = dataclasses.replace(
        base, burst_at=1, burst_n=5 if smoke else 8
    )
    return {"base": base, "storm": storm}


def run(smoke: bool = False):
    model, params = _model()
    wl = _workloads(smoke)
    base_trace = generate_arrivals(wl["base"])
    # seed-determinism: regeneration is bit-equal
    seed_det = [
        (a.step, a.prompt, a.max_new) for a in generate_arrivals(wl["base"])
    ] == [(a.step, a.prompt, a.max_new) for a in base_trace]
    assert seed_det, "workload regeneration drifted from its seed"

    rows: Dict[str, Dict[str, Any]] = {}
    reports: Dict[str, Dict[str, Any]] = {}

    def one(name: str, trace, ber: float, drain_interval: int = 0):
        engine = Engine(model, params, _serving_cfg(ber, drain_interval))
        rep = drive(engine, trace)
        reports[name] = rep
        rows[name] = {
            k: rep[k] for k in (
                "tokens_per_s", "p50_ms_per_token", "p99_ms_per_token",
                "ttft_p50_ms", "ttft_p99_ms", "scrubbed_bytes_per_token",
                "tokens_emitted", "n_preemptions", "n_host_syncs",
                "host_syncs_per_step", "steps",
            )
        }
        return rep

    rep0 = one("traffic_ber0", base_trace, 0.0)
    # determinism across fresh engines, not just fresh traces
    rep0b = drive(
        Engine(model, params, _serving_cfg(0.0)), generate_arrivals(wl["base"])
    )
    assert rep0b["token_streams"] == rep0["token_streams"], (
        "same seed + same config must replay the same tokens"
    )
    rep_ber = one("traffic_ber0.001", base_trace, 1e-3)
    rep_storm = one(
        "traffic_storm_ber0.001", generate_arrivals(wl["storm"]), 1e-3
    )
    assert rep_storm["n_preemptions"] > 0, (
        "the storm arm must actually preempt"
    )
    rep_desync = one(
        "traffic_desync_ber0.001", base_trace, 1e-3, drain_interval=1
    )
    # the desynchronized drain's contract, measured under real traffic:
    # identical tokens, strictly fewer blocking device->host readbacks
    desync_parity = rep_desync["token_streams"] == rep_ber["token_streams"]
    desync_fewer = rep_desync["n_host_syncs"] < rep_ber["n_host_syncs"]
    assert desync_parity, "drain_interval=1 drifted from the lockstep tokens"
    assert desync_fewer, (
        "the deferred drain must issue strictly fewer host syncs "
        f"({rep_desync['n_host_syncs']} vs {rep_ber['n_host_syncs']})"
    )
    flags = {
        "seed_deterministic": bool(seed_det),
        "desync_token_parity_ok": bool(desync_parity),
        "desync_fewer_syncs_ok": bool(desync_fewer),
    }
    return rows, flags


def main(smoke: bool = False, out: Optional[str] = None):
    print("# traffic: open-loop Poisson load over the serving engine;")
    print("# per-arm p50/p99 wall-clock per token, tokens/s, scrubbed")
    print("# bytes/token, host syncs; the desync arm must match the")
    print("# lockstep tokens with strictly fewer syncs")
    print("name,us_per_call,derived")
    rows, flags = run(smoke=smoke)
    for name, row in rows.items():
        us = 1e3 * row["p50_ms_per_token"]
        print(
            f"{name},{us:.1f},"
            f"tokens_per_s={row['tokens_per_s']:.1f};"
            f"p99_ms={row['p99_ms_per_token']:.2f};"
            f"ttft_p50_ms={row['ttft_p50_ms']:.2f};"
            f"scrubbed_bytes_per_token="
            f"{row['scrubbed_bytes_per_token']:.0f};"
            f"preempt={row['n_preemptions']};"
            f"syncs={row['n_host_syncs']};"
            f"syncs_per_step={row['host_syncs_per_step']:.2f}"
        )
    if out:
        from ._record import merge_record

        merge_record(out, "traffic", {"rows": rows, **flags}, smoke=smoke)


if __name__ == "__main__":
    main()
