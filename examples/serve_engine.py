"""Serving-engine quickstart: continuous batching over a paged KV pool in
approximate memory, with page-granular reactive repair.

A mixed workload — more concurrent requests than the page pool can hold at
once — runs through the full lifecycle (admit -> prefill -> decode ->
finish, with preemption under page pressure) while bit flips strike the
pool between steps.  Repair granularity is the knob under study:

  --repair page    scrub only the faulted pages among those each step
                   touched (the paper's reactive design, page-granular)
  --repair whole   scrub the entire pool whenever anything faulted (the
                   pre-engine scrub_cache baseline)

Run:  PYTHONPATH=src python examples/serve_engine.py [--ber 1e-3] [--requests 8]
"""
import argparse
import dataclasses
import time

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import ApproxConfig
from repro.serving import Engine, ServingConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--ber", type=float, default=1e-3)
    ap.add_argument("--repair", default="page", choices=["page", "whole", "off"])
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_config(args.arch).reduced(),
        n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16,
        d_ff=128, vocab=97,
        repair=ApproxConfig(mode="off"),   # the engine space owns repair
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # pool deliberately smaller than worst-case demand: 8 requests of up to
    # 5 pages each over a 10-page pool — admission control + preemption live
    engine = Engine(
        model,
        params,
        ServingConfig(
            page_size=4, n_pages=10, max_batch=4, max_pages_per_request=5,
            repair=args.repair, ber=args.ber,
            sweep_interval=8, sweep_pages=2, seed=3,
        ),
    )
    rids = []
    for i in range(args.requests):
        prompt = jax.random.randint(
            jax.random.PRNGKey(i), (5 + i % 3,), 1, 96
        )
        rids.append(engine.add_request(prompt, max_new=args.max_new))

    t0 = time.time()
    results = engine.run()
    dt = time.time() - t0

    m = engine.metrics()
    d = engine.stats_dict()
    print(f"arch={cfg.name} repair={args.repair} BER={args.ber:g}")
    print(
        f"served {len(results)} requests / {m['tokens_emitted']} tokens in "
        f"{dt:.1f}s ({1000 * dt / max(m['tokens_emitted'], 1):.0f} ms/token); "
        f"preemptions={m['n_preemptions']}"
    )
    print(
        f"pool: flips={d['flips']} repairs nan={d['nan_found']} "
        f"inf={d['inf_found']} events={d['events']}"
    )
    print(
        f"repair: {m['scrub_calls']} scrub calls "
        f"({m['reactive_scrubs']} reactive, {m['sweep_scrubs']} sweep), "
        f"{m['scrubbed_bytes_per_token']:.0f} scrubbed bytes/token, "
        f"{m['hot_pages']} pages ever charged an event"
    )
    first = results[rids[0]]
    print(f"request 0 continuation: {first['generated']}")


if __name__ == "__main__":
    main()
