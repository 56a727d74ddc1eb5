"""Architecture + run configuration schema.

One ``ArchConfig`` per assigned architecture lives in ``configs/<id>.py``
(exact numbers from the assignment table); ``reduced()`` derives the
CPU-smoke-test variant of the same family.  Shape cells (train_4k, …) are
defined here as the assignment's global shape table.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from ..runtime import ApproxConfig


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | vlm | audio | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int

    # attention / block details
    head_dim: Optional[int] = None      # default d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    norm: str = "rms"                   # rms | ln
    mlp: str = "swiglu"                 # swiglu | gelu
    tie_embeddings: bool = True

    # moe
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25

    # ssm / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    mamba_per_attn: int = 2             # zamba: mamba layers per shared-attn
    n_shared_blocks: int = 2            # zamba: alternating shared blocks
    slstm_every: int = 8                # xlstm: every k-th block is sLSTM

    # enc-dec
    enc_layers: int = 0
    dec_layers: int = 0

    # frontend stub ([vlm]/[audio]: assignment says modality frontend is a
    # stub feeding precomputed embeddings)
    frontend: str = "none"              # none | patches | frames
    frontend_fraction: float = 0.125    # fraction of seq that is frontend tokens

    # numerics
    dtype_name: str = "bfloat16"

    # the paper's technique, as one unified runtime config (README §Config;
    # a legacy core.repair.RepairConfig is accepted too — every consumer
    # reads only the shared mode/policy/include_inf/max_magnitude fields).
    # max_magnitude is the beyond-paper extension (README §Config): NaN-only
    # repair provably does not survive sustained BER in training — a flip on
    # a high exponent bit is a *legal float* (0.02 -> 5e3/8e7/1e38 for
    # successive bits) that poisons the loss one matmul later.  Healthy
    # weights/moments are O(1); single-bit exponent flips either stay within
    # ~8x (amortizable drift, deliberately kept) or jump >= ~5e3 — 1e3
    # separates the two regimes with huge margin.
    repair: ApproxConfig = ApproxConfig(
        mode="memory", policy="neighbor_mean", max_magnitude=1e3
    )

    # distribution knobs (per-arch defaults; launch may override)
    scan_layers: bool = True
    remat: bool = True
    attn_q_block: int = 512
    attn_kv_block: int = 1024
    ssm_chunk: int = 128

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def reduced(self) -> "ArchConfig":
        """Same family, laptop-scale — used by per-arch CPU smoke tests.

        f32 storage: the CPU backend cannot *execute* some bf16 batched dots
        (DotThunk), so full-size bf16 configs never execute on the CPU.
        They run on a TPU (``chip_smoke.py`` serves full-size qwen2-1.5b
        through the Engine) and are compiled for a described TPU by
        ``tests/test_tpu_compile.py`` and lowered by ``launch/dryrun.py``."""
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            dtype_name="float32",
            n_layers=min(self.n_layers, 4),
            d_model=128,
            n_heads=4,
            n_kv=min(self.n_kv, 2) if self.n_kv < self.n_heads else 4,
            head_dim=32,
            d_ff=256 if self.d_ff else 0,
            vocab=512,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32,
            mamba_per_attn=2,       # 4 reduced layers: 2 groups, no tail
            slstm_every=4,          # 4 reduced layers: 1 group of 3+1
            enc_layers=min(self.enc_layers, 2) if self.enc_layers else 0,
            dec_layers=min(self.dec_layers, 2) if self.dec_layers else 0,
            attn_q_block=64,
            attn_kv_block=64,
            ssm_chunk=16,
        )


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


# The assignment's shape table (shared by all 10 LM-family archs).
SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1),
}

# long_500k requires sub-quadratic context handling: only SSM/hybrid archs
# run it (README §Workloads records the skips for the 8 full-attention archs).
LONG_CONTEXT_FAMILIES = ("hybrid", "ssm")


def cells_for(cfg: ArchConfig):
    """The executed (arch × shape) cells for one architecture."""
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and cfg.family not in LONG_CONTEXT_FAMILIES:
            continue
        out.append(s)
    return out
