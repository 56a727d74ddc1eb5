"""Serving-engine bench: tokens/s and scrubbed-bytes/token across repair
granularities AND decode data paths, across BER points.

The paper's claim at serving granularity: reactive repair should pay
proportionally to what *faulted*, not to what is *resident*.  The engine
runs the same mixed prefill/decode workload (more concurrent requests than
the page pool can hold at once — admission control + preemption active)
under five arms:

  whole          any fault among the touched pages scrubs the entire pool
                 (the pre-engine ``scrub_cache`` baseline); gathered-view
                 prefill + decode
  page           only the faulted pages are scrubbed (reactive,
                 page-granular); gathered-view prefill + decode — the
                 PR-2/PR-4 gather path
  paged          page repair + the fused paged-attention kernel: decode
                 straight off the pool, detection fused into the read; the
                 prefill still gathers (the PR-5 half-fused row)
  prefill_paged  the full kernel family: chunked paged prefill + fused
                 decode — ZERO full-view copies across the whole request
                 lifecycle (README §Serving engine)
  split_k        the full family with split-K flash decoding: the 8-page
                 walk partitioned across grid cells, merged by log-sum-exp

CSV: name,us_per_call,derived — us_per_call is us/token (wall-clock);
derived carries scrubbed-bytes/token, the event counters, and the
pool-copy counts.  Asserted every run: at BER > 0 the page arm comes in
strictly below the whole arm on scrubbed-bytes/token; every fused arm is
*no worse* than the gather path — identical tokens emitted and no more
scrubbed bytes/token; the fully-fused arms issue ZERO full-view copies;
the split-K arm really resolves >1 splits.  Wall-clock is reported but not
asserted for the fused arms: off-TPU the Pallas kernels run in interpret
mode (a Python-level simulator), which says nothing about the lowered
kernels these arms exist for.

A fourth comparison runs the tiered-KV arms (README §Serving engine —
"Tiered KV"): the same storm workload with preemption resolved by
recompute (``host_pages=0``) vs swap through the host exact tier
(``swap_policy="swap"``).  Asserted every run: identical token streams at
BER=0 and the swap arm re-prefills *strictly fewer* tokens than the
recompute arm — the cost the tier exists to avoid.  A BER>0 swap row
records the boundary-scrub bytes/token the crossings ledger.

``main(out=...)`` merges ``serving`` and ``tiered_kv`` sections into the
shared bench record (``benchmarks/run.py --out BENCH_repair.json``),
validated by ``scripts/check_bench.py``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import ApproxConfig, ApproxSpace
from repro.serving import Engine, ServingConfig, engine_space

# single-bit flips on healthy f32 lanes only rarely land in the exponent's
# fatal pattern, so the BER points sit high enough that every run fires
# repair events (the zero point pins the no-fault overhead)
BERS = (0.0, 1e-4, 1e-3)
SMOKE_BERS = (0.0, 1e-3)

ARMS = ("whole", "page", "paged", "prefill_paged", "split_k")

# per-arm engine switches: (repair, paged_decode, paged_prefill, split_k)
_ARM_CFG = {
    "whole": ("whole", "off", "off", 1),
    "page": ("page", "off", "off", 1),
    "paged": ("page", "auto", "off", 1),
    "prefill_paged": ("page", "auto", "auto", 1),
    "split_k": ("page", "auto", "auto", 0),     # auto: M=8 -> 4 splits
}


def _model():
    cfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(),
        n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16,
        d_ff=128, vocab=97,
        repair=ApproxConfig(mode="off"),   # the engine space owns repair
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _workload(engine: Engine, n_requests: int, max_new: int):
    for i in range(n_requests):
        prompt = jax.random.randint(
            jax.random.PRNGKey(100 + i), (5 + i % 3,), 1, 96
        )
        engine.add_request(prompt, max_new=max_new)


def run(smoke: bool = False):
    model, params = _model()
    n_requests, max_new = (8, 6) if smoke else (10, 12)
    rows = []
    arm_metrics = {}
    for ber in SMOKE_BERS if smoke else BERS:
        per_mode = {}
        for arm in ARMS:
            repair, paged_decode, paged_prefill, split_k = _ARM_CFG[arm]
            engine = Engine(
                model,
                params,
                ServingConfig(
                    page_size=4, n_pages=10, max_batch=4,
                    max_pages_per_request=8,
                    repair=repair, paged_decode=paged_decode,
                    paged_prefill=paged_prefill, split_k=split_k,
                    ber=ber, sweep_interval=16, sweep_pages=2, seed=7,
                ),
                # the NaN/Inf rule: the engine's default range guard also
                # counts flips of the padding K/V that empty decode slots
                # write into the null page on the fused path only, which
                # would charge the fused arms an extra null-page scrub
                space=ApproxSpace(engine_space(model).config, max_magnitude=None),
            )
            if paged_decode == "auto":
                assert engine.paged_plan is not None, (
                    "fused decode must engage on the bench config"
                )
            if paged_prefill == "auto":
                assert engine._prefill_fn is not None, (
                    "fused prefill must engage on the bench config"
                )
            if arm == "split_k":
                assert engine._split_k > 1, (
                    "split-K must resolve >1 splits on the bench config"
                )
            _workload(engine, n_requests, max_new)
            # the first step pays trace + compile for every executable the
            # workload touches; timing it apart keeps us_per_token a
            # steady-state number instead of a compile-time artifact
            t0 = time.perf_counter()
            engine.step()
            warmup_us = 1e6 * (time.perf_counter() - t0)
            warm_toks = engine.tokens_emitted
            t0 = time.perf_counter()
            results = engine.run()
            dt = time.perf_counter() - t0
            assert len(results) == n_requests
            m = engine.metrics()
            d = engine.stats_dict()
            per_mode[arm] = {**m, "tokens": {
                rid: results[rid]["tokens"] for rid in results
            }}
            us_per_token = 1e6 * dt / max(m["tokens_emitted"] - warm_toks, 1)
            name = f"serving_{arm}_ber{ber:g}"
            rows.append((
                name,
                us_per_token,
                f"warmup_us={warmup_us:.0f};"
                f"scrubbed_bytes_per_token={m['scrubbed_bytes_per_token']:.0f};"
                f"tokens={m['tokens_emitted']};"
                f"preempt={m['n_preemptions']};events={d['events']};"
                f"flips={d['flips']};gathers={m['pool_gathers']};"
                f"scatters={m['pool_scatters']}",
            ))
            arm_metrics[name] = {
                "us_per_token": us_per_token,
                "warmup_us": warmup_us,
                "scrubbed_bytes_per_token": m["scrubbed_bytes_per_token"],
                "tokens_emitted": m["tokens_emitted"],
                "pool_gathers": m["pool_gathers"],
                "pool_scatters": m["pool_scatters"],
                "events": d["events"],
            }
        if ber > 0.0:
            assert (
                per_mode["page"]["scrubbed_bytes_per_token"]
                < per_mode["whole"]["scrubbed_bytes_per_token"]
            ), "page-granular repair must scrub strictly fewer bytes/token"
        # every fused arm is NO WORSE than the gather path: identical token
        # streams (same repair math, fused into the read) and no more
        # repair traffic
        for arm in ("paged", "prefill_paged", "split_k"):
            assert per_mode[arm]["tokens"] == per_mode["page"]["tokens"], (
                f"{arm} drifted from the gathered path"
            )
            assert (
                per_mode[arm]["scrubbed_bytes_per_token"]
                <= per_mode["page"]["scrubbed_bytes_per_token"]
            ), f"{arm} must not scrub more bytes/token than the gather path"
        assert per_mode["paged"]["pool_gathers"] < per_mode["page"]["pool_gathers"]
        # the fully-fused arms retire EVERY full-view copy — admission,
        # prefill and decode all run straight off the pool
        for arm in ("prefill_paged", "split_k"):
            assert per_mode[arm]["pool_gathers"] == 0, arm
            assert per_mode[arm]["pool_scatters"] == 0, arm
    return rows, arm_metrics


def _tiered_engine(ber: float, host_pages: int):
    return ServingConfig(
        page_size=4, n_pages=10, max_batch=4, max_pages_per_request=6,
        repair="page", ber=ber, sweep_interval=16, sweep_pages=2, seed=7,
        host_pages=host_pages,
    )


def run_tiered(smoke: bool = False):
    """Swap-vs-recompute under page pressure.  The BER=0 pair carries the
    acceptance assert (identical tokens, strictly fewer re-prefilled
    tokens); the BER>0 swap row records what the boundary scrubs cost."""
    model, params = _model()
    n_requests, max_new = (8, 6) if smoke else (10, 12)
    rows = []
    arm_metrics = {}
    tokens = {}

    def one(name: str, ber: float, host_pages: int):
        engine = Engine(model, params, _tiered_engine(ber, host_pages))
        _workload(engine, n_requests, max_new)
        # same warmup split as the serving rows: the first step carries
        # trace + compile, us_per_token reports the steady state
        t0 = time.perf_counter()
        engine.step()
        warmup_us = 1e6 * (time.perf_counter() - t0)
        warm_toks = engine.tokens_emitted
        t0 = time.perf_counter()
        results = engine.run()
        dt = time.perf_counter() - t0
        assert len(results) == n_requests
        m = engine.metrics()
        ts = engine.tier_stats()
        toks = max(m["tokens_emitted"], 1)
        row = {
            "us_per_token": 1e6 * dt / max(m["tokens_emitted"] - warm_toks, 1),
            "warmup_us": warmup_us,
            "tokens_emitted": m["tokens_emitted"],
            "prefill_tokens_recomputed": m["prefill_tokens_recomputed"],
            "boundary_scrub_bytes_per_token":
                ts.get("boundary_scrub_bytes", 0) / toks,
            "swap_outs": ts.get("swap_outs", 0),
            "swap_ins": ts.get("swap_ins", 0),
            "recompute_fallbacks": ts.get("recompute_fallbacks", 0),
            "n_preemptions": m["n_preemptions"],
        }
        tokens[name] = {rid: results[rid]["tokens"] for rid in results}
        rows.append((
            f"tiered_{name}_ber{ber:g}",
            row["us_per_token"],
            f"recomputed={row['prefill_tokens_recomputed']};"
            f"tokens={row['tokens_emitted']};"
            f"boundary_bytes_per_token="
            f"{row['boundary_scrub_bytes_per_token']:.0f};"
            f"swaps={row['swap_outs']}/{row['swap_ins']};"
            f"fallbacks={row['recompute_fallbacks']};"
            f"preempt={row['n_preemptions']}",
        ))
        arm_metrics[name] = row
        return row

    rec = one("tiered_recompute", 0.0, 0)
    swp = one("tiered_swap", 0.0, 12)
    # the storm must actually preempt, or the comparison measures nothing
    assert rec["n_preemptions"] > 0 and swp["n_preemptions"] > 0
    assert tokens["tiered_swap"] == tokens["tiered_recompute"], (
        "swap-in drifted from recompute at BER=0"
    )
    assert (
        swp["prefill_tokens_recomputed"] < rec["prefill_tokens_recomputed"]
    ), "the swap arm must re-prefill strictly fewer tokens than recompute"
    assert swp["swap_outs"] == swp["swap_ins"] > 0
    # under faults the crossings pay (and ledger) the boundary scrub
    faulted = one("tiered_swap_ber", 1e-3, 12)
    assert faulted["boundary_scrub_bytes_per_token"] > 0 or (
        faulted["swap_outs"] == 0
    )
    return rows, arm_metrics


def main(smoke: bool = False, out: Optional[str] = None):
    print("# serving_engine: continuous batching over the paged KV pool;")
    print("# us_per_call is us/token; page must beat whole on bytes/token;")
    print("# fused arms must match page tokens; prefill_paged/split_k run the")
    print("# whole lifecycle off the pool (zero full-view copies)")
    print("name,us_per_call,derived")
    rows, arm_metrics = run(smoke=smoke)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print("# tiered_kv: preemption swap vs recompute (README §Tiered KV);")
    print("# swap must re-prefill strictly fewer tokens at identical output")
    tiered_rows, tiered_metrics = run_tiered(smoke=smoke)
    for name, us, derived in tiered_rows:
        print(f"{name},{us:.1f},{derived}")
    if out:
        from ._record import merge_record

        merge_record(out, "serving", {
            "rows": arm_metrics,
            "paged_vs_gather_bytes_ok": True,   # asserted above
        }, smoke=smoke)
        merge_record(out, "tiered_kv", {
            "rows": tiered_metrics,
            "swap_beats_recompute_ok": True,    # asserted in run_tiered
        }, smoke=smoke)


if __name__ == "__main__":
    main()
