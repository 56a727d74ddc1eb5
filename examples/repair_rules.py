"""Heterogeneous protection with the `RepairRule` API (README §RepairRule).

One `RuleSet` expresses what used to take three deployments' worth of
config: optimizer state range-guarded and conservatively filled, KV-style
cache leaves NaN-only with cheap zero fill repaired reactively, and an
embedding table pinned to an ECC-like exact island — then the SAME rules
drive a boundary scrub, a reactive pass, and an injection window, with
per-rule counters in one ledger.

Run:  PYTHONPATH=src python examples/repair_rules.py
"""
import jax
import jax.numpy as jnp

from repro.core import stats as stats_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import (
    ApproxConfig, ApproxSpace, Detector, RepairRule, RuleSet,
)


def main():
    enable_compile_cache()
    rules = RuleSet((
        # optimizer moments: a flipped high exponent bit yields ~1e38 — a
        # legal float that destroys training.  Range-guard + tile-mean fill.
        (r"(^|/)opt(/|$)",
         RepairRule(detect=Detector(max_magnitude=1e3),
                    fill="neighbor_mean")),
        # KV pages: activations are not O(1), so NaN-only detection; zero
        # fill is fine (masked softmax lanes); repair reactively, not at
        # every step boundary.
        (r"(^|/)(k|v)(/|$)",
         RepairRule(detect=Detector(inf=False), fill="zero",
                    trigger="reactive")),
        # embeddings: "exact via stronger correction" as just another rule.
        (r"(^|/)embed(/|$)", RepairRule.exact_rule(label="embed-exact")),
    ))
    space = ApproxSpace(ApproxConfig(mode="memory", rules=rules, ber=1e-4))

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    state = {
        "params": {"w": jax.random.normal(k1, (64, 64))},
        "opt": {"mu": jax.random.normal(k2, (64, 64))},
        "k": jax.random.normal(k3, (16, 64)),
        "embed": {"table": jnp.ones((32, 16))},
    }

    # one injection window — the exact island is never struck
    state, flips = space.inject(state, jax.random.fold_in(key, 1))
    print(f"injection window: {int(flips)} flips "
          f"(embed untouched: "
          f"{bool((state['embed']['table'] == 1.0).all())})")

    # poison representative lanes per protection class
    state["opt"]["mu"] = state["opt"]["mu"].at[0, 0].set(4e4)   # legal float!
    state["k"] = state["k"].at[1, 2].set(jnp.nan)
    state["params"]["w"] = state["params"]["w"].at[3, 3].set(jnp.inf)

    # boundary pass: the reactive KV rule holds its fire
    state, st = space.scrub(state, stats_lib.zeros(), trigger="boundary")
    print(f"boundary scrub: opt range-guard fired "
          f"(|mu[0,0]| now {abs(float(state['opt']['mu'][0, 0])):.3f}), "
          f"kv NaN still resident: {bool(jnp.isnan(state['k'][1, 2]))}")

    # reactive pass: now the KV rule repairs
    state, st = space.scrub(state, st, trigger="reactive")
    print(f"reactive pass: kv clean: "
          f"{bool(jnp.isfinite(state['k']).all())}")

    space.record(st)                 # fold the threaded stream back in
    print("\nper-rule ledger (one unified definition across passes):")
    for label, counters in space.rule_stats().items():
        print(f"  {label:24s} {counters}")
    print(f"aggregate stream: {space.stats_dict()}")


if __name__ == "__main__":
    main()
