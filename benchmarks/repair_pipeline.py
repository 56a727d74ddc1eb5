"""Repair-pipeline bench: eager vs compiled scrub/inject, 1 vs 8 devices.

The PR-3 trajectory bootstrap (ISSUE 3): wall-time per scrub/inject call and
scrubbed-bytes/step for

  * the pre-refactor **eager** path (per-leaf jnp dispatch: `scrub_tree` /
    `inject_tree` called op-by-op from the host), vs
  * the mesh-native **compiled** path (`ApproxSpace` dispatching one cached
    donated executable per state layout),

on this process's devices and — via a CPU-pinned subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — on 8 fake host
devices with the state FSDP-sharded, where the executable repairs
shard-locally.  Every section records its ``backend``; the timings are
host-clock numbers of that backend, never device metrics.  Acceptance:
compiled ≤ eager at smoke shapes (asserted).

CSV: ``name,us_per_call,scrubbed_mb_per_step``; ``main(out=...)`` writes the
full record to JSON (``benchmarks/run.py --out BENCH_repair.json``).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def _tree(n: int, key) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    return {
        "params": {"w": jax.random.normal(k1, (n, n), jnp.float32)},
        "opt": {"mu": jax.random.normal(k2, (n, n), jnp.float32),
                "step": jnp.zeros((), jnp.int32)},
    }


def _sharded(tree):
    """FSDP-style placement over all local devices (row-sharded matrices)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))

    def put(leaf):
        spec = P("data") if (
            leaf.ndim and leaf.shape[0] % jax.device_count() == 0
        ) else P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree), mesh


def _time(fn, reps: int) -> float:
    """Median wall-time per call in µs (one untimed warmup)."""
    fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(times)


def measure(n: int, reps: int, *, shard: bool = False) -> Dict[str, Any]:
    from repro.core import stats as stats_lib
    from repro.runtime import ApproxConfig, ApproxSpace
    from repro.runtime.space import inject_tree, scrub_tree

    ber = 1e-6
    tree = _tree(n, jax.random.PRNGKey(0))
    mesh = None
    if shard:
        tree, mesh = _sharded(tree)
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero", ber=ber))
    if mesh is not None:
        space.use_mesh(mesh)
    regions = space.regions_for(tree)
    key = jax.random.PRNGKey(1)

    def eager_scrub():
        out, _ = scrub_tree(tree, space.config, stats_lib.zeros(), regions)
        jax.block_until_ready(out)

    def eager_inject():
        out, flips = inject_tree(tree, key, ber, regions)
        jax.block_until_ready((out, flips))

    # compiled: ping-pong with donated buffers — the production pattern
    # (the scrubbed/flipped tree replaces the resident state)
    state = {"scrub": jax.tree.map(jnp.copy, tree),
             "inject": jax.tree.map(jnp.copy, tree)}

    def compiled_scrub():
        state["scrub"], _ = space.scrub(
            state["scrub"], stats_lib.zeros(), donate=True
        )
        jax.block_until_ready(state["scrub"])

    def compiled_inject():
        state["inject"], _ = space.inject(
            state["inject"], key, ber, record=False, donate=True
        )
        jax.block_until_ready(state["inject"])

    bytes0 = space.scrubbed_bytes
    res = {
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "placement": space.plan_for(tree).placement,
        "shape": [n, n],
        "eager_scrub_us": _time(eager_scrub, reps),
        "compiled_scrub_us": _time(compiled_scrub, reps),
        "eager_inject_us": _time(eager_inject, reps),
        "compiled_inject_us": _time(compiled_inject, reps),
        "traces": space.n_traces,
    }
    res["scrubbed_bytes_per_step"] = (
        (space.scrubbed_bytes - bytes0) // (reps + 1)
    )
    return res


def _measure_subprocess(n: int, reps: int, devices: int) -> Optional[Dict]:
    """Re-run this module under ``devices`` fake host devices.

    The child is pinned to the CPU backend: its fake devices are a CPU
    rehearsal of the sharded placement, and a parent that has touched JAX
    holds the accelerator, so a child reaching for it would contend for
    the chip.  Its section records ``"backend": "cpu"``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.repair_pipeline",
         "--emit-json", "--n", str(n), "--reps", str(reps), "--shard"],
        capture_output=True, text=True, env=env, cwd=root, timeout=600,
    )
    if proc.returncode != 0:
        print(f"# 8-device subprocess failed:\n{proc.stderr[-2000:]}",
              file=sys.stderr)
        return None
    return json.loads(proc.stdout.splitlines()[-1])


def main(smoke: bool = False, out: Optional[str] = None) -> Dict[str, Any]:
    n = 256 if smoke else 1024
    reps = 10 if smoke else 30
    record: Dict[str, Any] = {"smoke": smoke, "sections": {}}

    one = measure(n, reps)
    record["sections"]["devices_1"] = one
    eight = _measure_subprocess(n, reps, devices=8)
    if eight is None:
        # the 8-device half of the acceptance criterion must never be
        # skipped silently — fail the section so CI fails
        raise RuntimeError(
            "8-fake-device bench subprocess failed (stderr above); the "
            "compiled<=eager criterion is unverified on the multidev config"
        )
    record["sections"]["devices_8"] = eight

    for name, sec in record["sections"].items():
        mb = sec["scrubbed_bytes_per_step"] / 1e6
        label = f"{name}[{sec['backend']}]"
        for kind in ("scrub", "inject"):
            print(f"{label}/eager_{kind},{sec[f'eager_{kind}_us']:.1f},{mb:.3f}")
            print(
                f"{label}/compiled_{kind},"
                f"{sec[f'compiled_{kind}_us']:.1f},{mb:.3f}"
            )

    # acceptance: the compiled pipeline is never slower than the eager
    # per-leaf dispatch it replaced (ISSUE 3)
    for name, sec in record["sections"].items():
        for kind in ("scrub", "inject"):
            eager, compiled = sec[f"eager_{kind}_us"], sec[f"compiled_{kind}_us"]
            assert compiled <= eager, (
                f"{name}: compiled {kind} ({compiled:.1f}us) slower than "
                f"eager ({eager:.1f}us)"
            )
    print(f"# compiled <= eager holds on {len(record['sections'])} device "
          "configurations")

    if out:
        # merge alongside the other sections' records (serving engine) —
        # these keys stay at the top level for check_bench compatibility
        from ._record import merge_record

        for name, sec in record["sections"].items():
            merge_record(out, name, sec, smoke=smoke)
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--emit-json", action="store_true",
                    help="measure this process only; print one JSON line")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shard", action="store_true")
    args = ap.parse_args()
    if args.emit_json:
        print(json.dumps(measure(args.n, args.reps, shard=args.shard)))
    else:
        main(smoke=args.smoke, out=args.out)
