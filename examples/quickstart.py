"""Quickstart: the paper's experiment in 60 seconds, on the `ApproxSpace` API.

Reproduces the core demonstration (paper §4 / Fig. 1 / Table 3):

  1. a single bit-flip NaN in a matrix operand poisons a whole output row;
  2. the fused-repair matmul kernel prevents it, pre-MXU, for free;
  3. register mode re-fires on every reuse, memory mode repairs the origin
     exactly once (Table 3) — and every event, jnp-level or fused-kernel,
     lands in ONE unified stats stream owned by the `ApproxSpace`.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import ApproxConfig, ApproxSpace


def main():
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    n = 512
    a = jax.random.normal(k1, (n, n), jnp.float32)
    b = jax.random.normal(k2, (n, n), jnp.float32)

    # One runtime object owns regions, repair, injection, and stats.
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero", ber=1e-6))

    # -- 0. the simulation boundary --------------------------------------
    # inject() flips bits over the approximate region at the config's BER
    # and records the ground-truth count in the unified `flips` counter.
    _, flips = space.inject(a, jax.random.fold_in(key, 7), ber=1e-5)
    print(f"one approximate-memory window at BER 1e-5: {int(flips)} bit "
          f"flips (ground truth, recorded in unified stats)")

    # -- 1. the failure the paper describes ------------------------------
    # Force exactly one NaN pattern (paper §4 setup: a flip completing the
    # all-ones exponent) so the poisoning is deterministic.
    from repro.core import injection
    a_bad = injection.inject_nan(k3, a, 1)          # one flipped exponent
    c_poisoned = a_bad @ b
    n_nan = int(jnp.isnan(c_poisoned).sum())
    print(f"plain matmul with ONE NaN operand -> {n_nan} NaN outputs "
          f"({100.0 * n_nan / c_poisoned.size:.1f}% of the result)")

    # -- 2. reactive fused repair (kernel events -> unified stats) -------
    res = ops.repair_matmul(a_bad, b, mode="memory", policy="zero",
                            blocks=(128, 128, 256))
    space.record_kernel(res.counts)
    print(f"repair_matmul      -> finite: {bool(jnp.isfinite(res.c).all())}, "
          f"events: {int(res.counts[ops.MM_EV_TOTAL])}, "
          f"origin scrubbed: {not bool(jnp.isnan(res.a).any())}")

    # deviation from the clean product: bounded, amortizable drift
    err = float(jnp.max(jnp.abs(res.c - a @ b)))
    print(f"max |error| vs clean product: {err:.3f} "
          f"(bounded by the repaired lane's contribution)")

    # -- 3. Table 3: register vs memory over repeated consumption --------
    print("\nreuse  register-events  memory-events   (paper Table 3)")
    a_reg = a_mem = a_bad
    for i in range(4):
        r = ops.repair_matmul(a_reg, b, mode="register", blocks=(128, 128, 256))
        m = ops.repair_matmul(a_mem, b, mode="memory", blocks=(128, 128, 256))
        space.record_kernel(r.counts)
        space.record_kernel(m.counts)
        a_reg, a_mem = r.a, m.a
        print(f"  {i}        {int(r.counts[ops.MM_EV_TOTAL]):3d}             "
              f"{int(m.counts[ops.MM_EV_TOTAL]):3d}")
    print("\nregister mode pays on every reuse; memory mode paid once.")

    # -- 4. the memory-mode mechanism at the pytree level ----------------
    # scrub() is the same write-back the train step installs at its boundary.
    clean = space.scrub({"w": a_bad})
    print(f"space.scrub repaired the resident buffer: "
          f"{not bool(jnp.isnan(clean['w']).any())}")

    print(f"\nunified stats (flips + jnp + fused-kernel events in one "
          f"stream): {space.stats_dict()}")


if __name__ == "__main__":
    main()
