"""Flash attention (online softmax) with fused reactive KV repair.

The serving-path hot spot: in long-context decode/prefill the KV cache is by
far the largest approximate-memory resident (hundreds of GB at the
decode_32k/long_500k cells), and a NaN in one cached key poisons the softmax
of *every future query* that attends to it — the temporal version of the
paper's Fig. 1 row-poisoning.  As with repair_matmul, there is no trap to
catch on TPU, so the repair is fused into the tile load the kernel performs
anyway:

  * K/V tiles are bit-pattern checked + repaired in VMEM right after the
    HBM→VMEM DMA, before the q·kᵀ MXU op.  Zero extra HBM traffic.
  * Event counters per operand (Table 3 analogue).
  * register mode: cache keeps its NaN, every attention call re-repairs.
  * memory mode (ops.py): non-zero event count triggers one in-place scrub
    of the cache at its origin (reactive write-back) — one repair, ever.

Layout: q (B, H, S, D), k/v (B, Kh, T, D) with GQA mapping h → h // group.
Grid (B, H, S/bq, T/bk), kv-block innermost; scratch carries the online
softmax state (acc, running max m, running denom l) across the kv dimension.
Causal masking by global block positions; fully-masked tiles are skipped
(their DMA still happens — the skip saves VPU/MXU work, matching how a real
flash kernel prunes the upper triangle).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import tiling
from . import common

NEG_INF = -1e30

# counts layout (int32[8]): nan_k, inf_k, ev_k, nan_v, inf_v, ev_v, ev_total, pad
NAN_K, INF_K, EV_K, NAN_V, INF_V, EV_V, EV_TOTAL = range(7)


def _flash_kernel(
    consts_ref, q_ref, k_ref, v_ref, o_ref, counts_ref, acc_ref, m_ref, l_ref,
    *, causal: bool, sm_scale: float, policy: str, constant: float,
    bq: int, bk: int, nk: int, out_dtype,
):
    # consts_ref: scalar-prefetch detector constants (int32[2, 8], SMEM) —
    # row 0 for K tiles, row 1 for V tiles (dtypes may differ).
    b, h = pl.program_id(0), pl.program_id(1)
    qi, kj = pl.program_id(2), pl.program_id(3)
    step = (
        (b * pl.num_programs(1) + h) * pl.num_programs(2) + qi
    ) * pl.num_programs(3) + kj

    @pl.when(step == 0)
    def _init_counts():
        common.zero_counts(counts_ref, 8)

    @pl.when(kj == 0)
    def _init_state():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal pruning: is any (q, k) pair in this tile pair unmasked?
    q_last = qi * bq + bq - 1
    k_first = kj * bk
    live = (not causal) or (k_first <= q_last)

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)                     # (bq, D)
        # ---- fused reactive repair of the cached K/V tiles ----
        k_fixed, nan_k, inf_k = common.repair_tile(
            k_ref[0, 0], policy=policy, constant=constant,
            consts=common.consts_row(consts_ref, 0),
        )
        v_fixed, nan_v, inf_v = common.repair_tile(
            v_ref[0, 0], policy=policy, constant=constant,
            consts=common.consts_row(consts_ref, 1),
        )
        ev_k = ((nan_k + inf_k) > 0).astype(jnp.int32)
        ev_v = ((nan_v + inf_v) > 0).astype(jnp.int32)
        counts_ref[NAN_K] += nan_k
        counts_ref[INF_K] += inf_k
        counts_ref[EV_K] += ev_k
        counts_ref[NAN_V] += nan_v
        counts_ref[INF_V] += inf_v
        counts_ref[EV_V] += ev_v
        counts_ref[EV_TOTAL] += ((ev_k + ev_v) > 0).astype(jnp.int32)

        s = jax.lax.dot_general(
            q, k_fixed.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                             # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_ref[:, 0]                                     # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])                          # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                          # (bq,)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_fixed.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kj == nk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(out_dtype)


_pick = tiling.fit      # block fit — one definition repo-wide


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "policy", "constant", "include_inf", "interpret", "blocks",
        "detector",
    ),
)
def flash_attention_raw(
    q: jax.Array,   # (B, H, S, D)
    k: jax.Array,   # (B, Kh, T, D)
    v: jax.Array,   # (B, Kh, T, D)
    *,
    causal: bool = True,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    blocks: Optional[Tuple[int, int]] = None,
    detector=None,
) -> Tuple[jax.Array, jax.Array]:
    """Online-softmax attention with fused K/V tile repair (register-mode
    core; ops.flash_attention adds reactive memory-mode write-back).

    ``detector`` (a ``core.rules.Detector``) picks the fatal-pattern set for
    the cached K/V tiles; its constants ride in as a scalar-prefetch
    operand.  Returns (out (B,H,S,D), counts int32[8])."""
    if interpret is None:
        interpret = common.default_interpret()
    det = common.resolve_detector(detector, include_inf)
    B, H, S, D = q.shape
    _, Kh, T, _ = k.shape
    assert H % Kh == 0, (H, Kh)
    group = H // Kh
    bq, bk = blocks if blocks is not None else (_pick(S, 512), _pick(T, 512))
    nk = T // bk
    grid = (B, H, S // bq, nk)
    sm_scale = 1.0 / math.sqrt(D)

    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # the detector-constants operand
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, c: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda b, h, i, j, c, g=group: (b, h // g, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda b, h, i, j, c, g=group: (b, h // g, j, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, c: (b, h, i, 0)),
            common.smem_spec(),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    out, counts = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal,
            sm_scale=sm_scale,
            policy=policy,
            constant=constant,
            bq=bq,
            bk=bk,
            nk=nk,
            out_dtype=q.dtype,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        ],
        interpret=interpret,
    )(
        jnp.stack([
            common.detector_operand(det, k.dtype),
            common.detector_operand(det, v.dtype),
        ]),
        q, k, v,
    )
    return out, counts
