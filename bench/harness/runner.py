"""One run of one cell: set up, warm up, measure, check, print one line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run needs the accelerator the cell names and exits non-zero, printing
no result, without it.  Set-up builds the configuration's weights on the
device from the seed, the engine, and warms every program the cell's
traffic will run (decode step, prefill chunk, page allocation sizes, and at
BER > 0 the page scrub buckets and the injection pass), with JAX's
persistent compilation cache at ``<checkout>/.jax_cache``.  Then a lead-in
of traffic, the measured window, and the check against the plain reference
(``harness/check.py``).  The last line of stdout is one JSON object; the
numbers compared are the last lines of stderr and the result's last key.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from . import check, counts, stats, traffic
from .registry import Registry

ROOT = pathlib.Path(__file__).resolve().parents[2]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]        # the BENCHMARK.json entry
    spec: Dict[str, Any]            # bench/cells/<name>.json
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    shapes: counts.Shapes

    @property
    def ber(self) -> float:
        return float(self.spec["ber"])

    @property
    def engine(self) -> Dict[str, Any]:
        return self.spec["engine"]

    @property
    def max_seq(self) -> int:
        return self.engine["page_size"] * self.engine["max_pages_per_request"]

    def dose(self) -> float:
        """BER of one injection pass: ``inject_every`` steps' worth."""
        k = int(self.spec.get("inject_every", 1))
        return 1.0 - (1.0 - self.ber) ** k if self.ber > 0 else 0.0


def load_cell(reg: Registry, name: str) -> Cell:
    w = reg.workload(name)
    spec = reg.cell(name)
    if spec["config"] != w["config"] or spec["traffic"] != w["traffic"]:
        raise ValueError(f"cell file {name} disagrees with BENCHMARK.json")
    cfg = reg.config(spec["config"])
    if cfg.get("memory") == "exact" and float(spec["ber"]) > 0:
        raise ValueError(f"cell {name} flips bits in exact memory")
    mix = reg.traffic(spec["traffic"])
    return Cell(name, w, spec, cfg, mix, counts.Shapes.from_config(cfg))


def require_chip(n: int):
    """The accelerator devices, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"bench: cell needs {n} chips, JAX found {len(devices)}")
    return devices


def import_system():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401 — fails outside a checkout of the repository


def enable_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
def build(cell: Cell, seed: int):
    """The configuration's model, its weights from ``seed`` and an engine
    at BER 0 (the harness injects itself, outside the clock)."""
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    from repro.models import build_model
    from repro.runtime import ApproxConfig
    from repro.serving import Engine, ServingConfig

    from . import weights

    arch = ArchConfig(**cell.config["system"], repair=ApproxConfig(mode="off"))
    model = build_model(arch)
    params = weights.make(cell.config, seed, jnp.dtype(arch.dtype_name))
    weights.check_layout(params, model.abstract_params())
    geo = cell.engine
    engine = Engine(model, params, ServingConfig(
        page_size=geo["page_size"], n_pages=geo["n_pages"],
        max_batch=geo["max_batch"],
        max_pages_per_request=geo["max_pages_per_request"],
        prefill_chunk=geo["prefill_chunk"], repair=geo["repair"],
        ber=0.0, seed=seed % (2 ** 31),
    ))
    return engine


def warm(engine, cell: Cell, arrivals, seed: int) -> None:
    """Compile (or load) every program the window will run, and no other:
    the prefill chunk and decode step (one request through the engine), the
    page-reset program for every allocation size the mix asks for, and at
    BER > 0 the injection pass, the page scrub's power-of-two buckets and
    the id-vector conversion for every scrub-set size up to
    ``warm_scrub_ids`` (the pool converts each id list on the device)."""
    import jax
    import jax.numpy as jnp

    from . import loop, weights

    t0 = time.perf_counter()
    chunk = cell.engine["prefill_chunk"]
    # two at once: the prefill lane then sums two requests' counters
    rids = [engine.add_request([1] * (chunk + chunk // 2), max_new=3)
            for _ in range(2)]
    engine.run()
    for rid in rids:
        engine.results.pop(rid)
    t1 = time.perf_counter()
    page = cell.engine["page_size"]
    sizes = sorted({-(-len(a.prompt) // page) for a in arrivals} | {1})
    for n in sizes:
        engine.pool.free(engine.pool.alloc(n))
    t2 = time.perf_counter()
    if cell.ber > 0:
        rows = cell.engine["n_pages"] + 1
        b = 1
        while True:
            ids = list(range(min(b, rows)))
            engine._stream = engine.pool.scrub_pages(ids, engine._stream)
            if b >= rows:
                break
            b *= 2
        for n in range(1, int(cell.spec.get("warm_scrub_ids", 0)) + 1):
            jnp.asarray(list(range(n)), jnp.int32)
        loop.inject(engine, weights.seed_key(seed, 3), cell.dose(),
                    loop.Log(window=(0.0, 0.0)))
    # the loop's own first calls: the injection key split, the counters
    _, _ = jax.random.split(weights.seed_key(seed, 4))
    engine.metrics()
    engine.stats_dict()
    jax.block_until_ready(engine.pool.tree)
    log(f"warm-up: engine steps {t1 - t0:.3f} s, {len(sizes)} allocation sizes "
        f"{t2 - t1:.3f} s, scrub and injection {time.perf_counter() - t2:.3f} s")


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------
class Compiles:
    """Backend compilations by program name since ``reset()``.  JAX cannot
    remove a listener, so one is registered per process and feeds the
    counter last reset."""

    _current = None

    def __init__(self):
        import jax

        if Compiles._current is None:
            jax.monitoring.register_event_duration_secs_listener(Compiles._on_event)
        Compiles._current = self
        self.counts: Dict[str, int] = {}

    @staticmethod
    def _on_event(event, *_, fun_name="?", **__):
        if event == "/jax/core/compile/backend_compile_duration":
            c = Compiles._current.counts
            c[fun_name] = c.get(fun_name, 0) + 1

    def reset(self) -> Dict[str, int]:
        counts, self.counts = self.counts, {}
        return counts

@dataclasses.dataclass
class Run:
    """What the metric readers see (``bench/metrics/<name>.py``)."""

    cell: Cell
    log: Any
    setup_s: float
    peaks: Dict[str, Any]
    trace: Any = None


def verify(cell: Cell, reg: Registry, seed: int, finished, tainted, *,
           control: bool = False) -> Dict[str, Any]:
    """Regenerate the weights from the seed and compare the sample of the
    requests no unrepaired flip reached.  With ``control``, also compare up
    to ``check.N_TAINTED`` of the others, marked, which no verdict reads."""
    import jax
    import jax.numpy as jnp

    from . import weights

    c = cell.spec["correct"]
    clean = {r: v for r, v in finished.items() if r not in tainted}
    rids = check.sample(clean, seed, c["sample_tokens"], c["min_requests"],
                        c["max_requests"])
    reqs = [dict(finished[r], rid=r) for r in rids]
    if control:
        hit = check.sample({r: v for r, v in finished.items() if r in tainted},
                           seed, 0, check.N_TAINTED, check.N_TAINTED)
        reqs += [dict(finished[r], rid=r, tainted=True) for r in hit]
    w = weights.make(cell.config, seed, jnp.dtype(cell.config["system"]["dtype_name"]))
    ref = reg.reference(cell.config["reference"])
    with jax.default_matmul_precision("highest"):
        out = check.gaps(ref, cell.config, w, reqs, max_seq=cell.max_seq,
                         r_pad=int(cell.traffic["output"]["max"]), control=control)
    del w
    return out


def run_cell(reg: Registry, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, rate: Optional[float] = None,
             control: bool = False, need_chip: bool = True,
             keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """One run.  With ``control`` the verdict judges the control's tokens
    (the reference in float8) in place of the served ones, and the result
    adds the program's own reading and verdict under ``program``."""
    cell = load_cell(reg, name)
    if rate is not None:
        cell.traffic = dict(cell.traffic,
                            arrivals=dict(cell.traffic["arrivals"], rate_rps=rate))
    devices = require_chip(cell.workload["chips"]) if need_chip else None
    import_system()
    import jax

    from . import loop, weights
    from .peaks import peaks_for

    if devices is None:
        devices = jax.devices()
    log(f"compile cache: {enable_cache()}")
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if need_chip else {}
    lead_in = float(cell.traffic["lead_in_s"])
    arrivals = traffic.schedule(cell.traffic, seed, lead_in, seconds,
                                cell.config["vocab_size"])
    compiles = Compiles()
    t0 = time.perf_counter()
    engine = build(cell, seed)
    log(f"imports {t0 - t_start:.3f} s, weights and engine "
        f"{time.perf_counter() - t0:.3f} s")
    warm(engine, cell, arrivals, seed)
    log(f"compiled in set-up (cache misses): {sum(compiles.reset().values())}")
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s on {len(devices)} x {dev.device_kind}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    lg = loop.drive(
        engine, arrivals, lead_in_s=lead_in, seconds=seconds,
        ber_dose=cell.dose(), inject_every=int(cell.spec.get("inject_every", 1)),
        inject_key=weights.seed_key(seed, 2), trace_dir=trace_dir,
    )
    in_window = compiles.reset()
    n_compiles = sum(in_window.values())
    if n_compiles:
        log(f"compiled inside the window: {sorted(in_window.items(), key=lambda kv: -kv[1])[:12]}")
    finished = {
        rid: {"prompt": r["tokens"][: len(r["tokens"]) - len(r["generated"])],
              "generated": r["generated"]}
        for rid, r in engine.results.items()
    }
    nonfinite = engine.metrics()["nonfinite_logit_rows"]
    stats_d = engine.stats_dict()
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    run = Run(cell=cell, log=lg, setup_s=setup_s, peaks=peaks)
    if trace_dir:
        from . import trace as trace_lib

        record = trace_lib.extract(trace_dir)
        log(f"trace device events: {len(record['device']['ops'])} ops, "
            f"{len(record['device']['modules'])} modules, {len(record['host'])} host")
        if keep_trace:
            pathlib.Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            pathlib.Path(keep_trace).write_text(json.dumps(record))
        run.trace = trace_lib.Trace(record)
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in reg.metrics_for(name, kind):
        v = reg.metric(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    n_due = len(lg.due)
    late = sorted(lg.lateness) or [0.0]
    log(f"window {seconds} s: {n_due} requests due, {len(finished)} finished in the "
        f"run, {lg.window_steps[1] - lg.window_steps[0]} steps; submission late by "
        f"p50 {stats.percentile(late, 50) * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms; "
        f"injection {lg.n_injections} passes {lg.inject_s:.3f} s of {lg.real_s:.3f} s "
        f"real; compiles in the window {n_compiles}; flips {stats_d.get('flips')}")
    log(f"metrics: {json.dumps(metrics)}")
    log(f"counters at open {lg.counters.get('open')} and close {lg.counters['close']}; "
        f"steps traced {lg.traced_steps}; trace stop paused {lg.paused_s:.3f} s")
    tainted = lg.tainted & set(finished)
    log(f"unrepaired flips reached {len(tainted)} of {len(finished)} finished "
        f"requests ({len(lg.tainted)} of all); left out of the comparison")
    # the system's state goes before the reference runs
    del engine
    gc.collect()
    t0 = time.perf_counter()
    cmp = verify(cell, reg, seed, finished, tainted, control=control)
    log(f"reference: {time.perf_counter() - t0:.3f} s; per request (id, served "
        "tokens, widest gap, argmax mismatches"
        + (", control's widest gap" if control else "") + "): "
        + json.dumps([[r["rid"], r["n"], r["widest"], r["mismatch"]]
                      + ([r["control_widest"]] if control else [])
                      + (["tainted"] if r.get("tainted") else [])
                      for r in cmp["requests"]]))
    limit = cell.spec["correct"]["limit"]
    judged = [r for r in cmp["requests"] if not r.get("tainted")]
    gap, correct = check.verdict(judged, limit, "widest")
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "nonfinite_logit_rows": {"value": nonfinite, "limit": 0},
    }
    failed = sum(1 for r in judged if r["widest"] > limit)
    program = {"logit_gap": gap, "correct": bool(correct and nonfinite == 0)}
    if control:
        gap, correct = check.verdict(judged, limit, "control_widest")
        checks["logit_gap"]["value"] = gap
        failed = sum(1 for r in judged if r["control_widest"] > limit)
    correct = correct and nonfinite == 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": n_due, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    if control:
        result["program"] = program
        result["per_request"] = cmp["requests"]
    result["checks"] = checks
    log(f"check logit_gap is the widest gap over {len(judged)} requests"
        + (" (the control's tokens in place of the served)" if control else ""))
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return result


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="python3 -m bench.run",
                                 description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the mix's request rate (knee sweeps only)")
    ap.add_argument("--keep-trace", default=None,
                    help="also write the extracted trace record to this JSON file")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    result = run_cell(Registry(), args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start, rate=args.rate,
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0
