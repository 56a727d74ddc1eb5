"""End-to-end driver: train a ~100M-parameter LM under approximate memory.

Three conditions over the same data/seed (paper §4 structure, applied to a
full training loop instead of one matmul):

  --repair off       bit flips accumulate; the run NaN-poisons
  --repair register  per-use repair: survives, pays detect+select every read
  --repair memory    step-boundary scrub + write-back: survives, one repair
                     per flip (the paper's recommendation)

The approximate-memory window (BER) strikes params + optimizer moments
between steps (core/injection.py simulates the relaxed-refresh DRAM the
paper targets; see the refresh→BER→energy table in benchmarks/energy_model).

Run:  PYTHONPATH=src python examples/train_approx_lm.py \
          [--steps 300] [--ber 1e-7] [--repair memory] [--arch qwen2-1.5b]
"""
import argparse
import dataclasses
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import make_optimizer, train_loop
from repro.models import build_model
from repro.runtime import ApproxConfig, ApproxSpace


def build_100m(arch: str, repair_mode: str) -> "ArchConfig":
    """~100M-param variant of the chosen family (CPU-trainable)."""
    cfg = get_config(arch)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-100m",
        n_layers=min(cfg.n_layers, 8),
        d_model=768,
        n_heads=12,
        n_kv=min(cfg.n_kv, 4) if cfg.n_kv < cfg.n_heads else 8,
        head_dim=64,
        d_ff=3072 if cfg.d_ff else 0,
        vocab=32768,
        n_experts=min(cfg.n_experts, 8) if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        dtype_name="float32",
        mamba_per_attn=2,
        slstm_every=4,
        repair=ApproxConfig(
            mode=repair_mode, policy="neighbor_mean", max_magnitude=1e3
        ),
        attn_q_block=128,
        attn_kv_block=128,
        ssm_chunk=64,
    )


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ber", type=float, default=1e-8)
    ap.add_argument("--repair", default="memory",
                    choices=["off", "register", "memory"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = build_100m(args.arch, args.repair)
    model = build_model(cfg)
    print(f"arch={cfg.name}  params={model.param_count():,}  "
          f"repair={args.repair}  BER={args.ber:g}")

    opt = make_optimizer(peak_lr=1e-3, warmup=20, total=args.steps)
    data = SyntheticStream(cfg, seed=0, batch=args.batch, seq=args.seq)
    mgr = CheckpointManager(args.ckpt_dir, keep=2, scrub=True)

    # One ApproxSpace owns the run: boundary scrub inside the jitted step,
    # injection window between steps, regions cached by treedef, one stats
    # stream (incl. the injection ground truth in `flips`).
    space = ApproxSpace(cfg.repair, ber=args.ber)

    t0 = time.time()
    state, hist = train_loop(
        model, opt, data,
        steps=args.steps,
        key=jax.random.PRNGKey(0),
        ber=args.ber,
        checkpoint_manager=mgr,
        checkpoint_every=args.ckpt_every,
        log_every=10,
        space=space,
    )
    dt = time.time() - t0

    print(f"\n{'step':>6} {'loss':>9} {'acc':>7} {'flips':>7} "
          f"{'repairs(nan/inf)':>18}")
    for h in hist:
        print(f"{h['step']:>6} {h['loss']:>9.4f} {h['accuracy']:>7.4f} "
              f"{h['flips']:>7} {h['nan_found']:>9}/{h['inf_found']}")
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({1000 * dt / args.steps:.0f} ms/step); "
          f"final checkpoint: step {mgr.latest_step()}")


if __name__ == "__main__":
    main()
