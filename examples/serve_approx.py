"""Serving under approximate memory: batched greedy decoding with a
protected KV cache, on the `ApproxSpace` API.

The KV cache is the dominant approximate-memory resident in serving
(README §Serving).  This example decodes a token batch while bit flips
strike the cache between steps, in two conditions:

  --repair register   every cache read repairs in-flight (per-step cost)
  --repair memory     reactive scrub of the cache when repairs fired
                      (one-shot, then clean — serving Table 3)

Run:  PYTHONPATH=src python examples/serve_approx.py [--tokens 48] [--ber 1e-6]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import build_serve_step, serve_space
from repro.models import build_model
from repro.runtime import ApproxConfig

from repro.core import stats as stats_lib


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--ber", type=float, default=1e-4)
    ap.add_argument("--repair", default="memory", choices=["register", "memory"])
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_config(args.arch).reduced(),
        repair=ApproxConfig(mode=args.repair, policy="neighbor_mean",
                            max_magnitude=1e3, ber=args.ber),
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq = args.tokens + 8

    # One runtime object for the serving cache: regions cached by treedef,
    # injection + scrub + stats unified.  serve_space() memory-forces the
    # scrub path so a poisoned cache is repairable in both conditions.
    space = serve_space(model)
    cache = model.init_cache(args.batch, max_seq)
    step_fn = jax.jit(space.wrap_serve_step(build_serve_step(model)))
    stats = stats_lib.zeros()

    tok = jnp.ones((args.batch, 1), jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    n_scrubs = 0
    for t in range(args.tokens):
        # approximate-memory window strikes the resident cache (simulation);
        # the ground-truth flip count lands in the unified `flips` counter
        cache, _ = space.inject(
            cache, jax.random.fold_in(jax.random.PRNGKey(9), t), args.ber
        )
        if args.repair == "memory":
            # reactive: scrub only when the previous step found something
            cache, stats2 = space.scrub(cache, stats)
            fired = int(stats2["events"]) > int(stats["events"])
            n_scrubs += int(fired)
            stats = stats2
        nxt, logits, cache, stats = step_fn(
            params, cache, {"tokens": tok}, jnp.asarray(t, jnp.int32), stats
        )
        assert bool(jnp.isfinite(logits).all()), "NaN reached the logits!"
        tok = nxt[:, None]
        out_tokens.append(tok)
    dt = time.time() - t0
    space.record(stats)        # fold the loop's functional stream into the space

    seq = jnp.concatenate(out_tokens, axis=1)
    d = space.stats_dict()
    print(f"arch={cfg.name} repair={args.repair} BER={args.ber:g}")
    print(f"decoded {args.tokens} tokens x batch {args.batch} in {dt:.1f}s "
          f"({1000 * dt / args.tokens:.0f} ms/token)")
    print(f"cache: flips={d['flips']} repairs nan={d['nan_found']} "
          f"inf={d['inf_found']} events={d['events']} scrub_passes={n_scrubs}")
    print(f"sample continuation (batch 0): {seq[0, :16].tolist()} ...")
    print("all logits finite: True")


if __name__ == "__main__":
    main()
