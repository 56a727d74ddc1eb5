"""Tiled MXU matmul with *fused reactive NaN repair* on the operand tiles.

This is the paper's mechanism relocated to where a TPU can afford it
(README §Runtime).  There is no per-instruction trap on a systolic array, and
post-consumption repair is useless (one NaN operand poisons a whole output
row — Fig. 1), so detection must happen **pre-consumption, on the operand
tile the kernel already loaded**:

  * Every a/b tile is bit-pattern checked and repaired *in VMEM* right after
    its HBM→VMEM DMA, before it enters the MXU.  The check is a handful of
    VPU compare/select ops on data that is already resident — it adds zero
    HBM traffic and hides under the MXU's O(bm·bn·bk) work.  This replaces
    the paper's SIGFPE *detection* step.

  * Event counters (the Table 3 analogue) accumulate per-operand NaN/Inf lane
    counts and tile-visit events into a tiny SMEM-resident output.  A visit
    of a poisoned tile == one "trap".

  * **register mode** stops there: the stored buffer keeps its NaN, so every
    visit of that tile re-detects and re-repairs — exactly the paper's
    register-repairing mechanism (N traps for an N×N matmul, Table 3).

  * **memory mode** (in ops.py) reacts to a non-zero event counter by
    scrubbing the poisoned operand *at its memory origin* (kernels/scrub.py,
    in-place aliased write-back), so every later consumption is clean — the
    paper's memory-repairing mechanism (exactly 1 repair).  The scrub runs
    under ``lax.cond``: when no event fired (the overwhelmingly common case)
    it costs nothing.  This is the precise TPU translation of "the signal is
    stolen and the NaN is repaired in main memory" — repair work happens only
    on an actual error, never proactively.

Provenance note: the paper back-traces the binary to find the faulting
address (>95 % success, Fig. 6).  Here the kernel *knows* the HBM tile it
loaded — origin recovery is structural and always succeeds (the counters
record which operand), which is the Fig. 6 number going to 100 % by
construction (see core/provenance.py for the jaxpr-level analysis).

Grid: (M/bm, N/bn, K/bk), k innermost, f32 VMEM scratch accumulator,
bf16/f32 operands, MXU-aligned default tiles (multiples of 128).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import tiling
from . import common

# counts layout (int32[8]):
#   0 nan_a   1 inf_a   2 ev_a (a-tile visits with ≥1 fatal lane)
#   3 nan_b   4 inf_b   5 ev_b
#   6 ev_total (visits where either operand had a fatal lane)   7 pad
NAN_A, INF_A, EV_A, NAN_B, INF_B, EV_B, EV_TOTAL = range(7)


def _mm_kernel(
    consts_ref, a_ref, b_ref, c_ref, counts_ref, acc_ref,
    *, policy: str, constant: float, nk: int,
    out_dtype,
):
    # consts_ref: scalar-prefetch detector constants (int32[8], SMEM) — the
    # fatal-pattern definition is an operand, not baked-in NaN-only logic.
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    step = (i * pl.num_programs(1) + j) * pl.num_programs(2) + k

    @pl.when(step == 0)
    def _init_counts():
        common.zero_counts(counts_ref, 8)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- fused reactive repair: operand tiles, pre-MXU ----
    # row 0: a's dtype constants; row 1: b's (operands may differ in dtype)
    a_fixed, nan_a, inf_a = common.repair_tile(
        a_ref[...], policy=policy, constant=constant,
        consts=common.consts_row(consts_ref, 0),
    )
    b_fixed, nan_b, inf_b = common.repair_tile(
        b_ref[...], policy=policy, constant=constant,
        consts=common.consts_row(consts_ref, 1),
    )
    ev_a = ((nan_a + inf_a) > 0).astype(jnp.int32)
    ev_b = ((nan_b + inf_b) > 0).astype(jnp.int32)
    counts_ref[NAN_A] += nan_a
    counts_ref[INF_A] += inf_a
    counts_ref[EV_A] += ev_a
    counts_ref[NAN_B] += nan_b
    counts_ref[INF_B] += inf_b
    counts_ref[EV_B] += ev_b
    counts_ref[EV_TOTAL] += ((ev_a + ev_b) > 0).astype(jnp.int32)

    # ---- MXU work ----
    acc_ref[...] += jnp.dot(
        a_fixed, b_fixed, preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        c_ref[...] = acc_ref[...].astype(out_dtype)


_pick = tiling.fit      # MXU-aligned block fit — one definition repo-wide


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "constant", "include_inf", "interpret", "blocks",
        "out_dtype", "detector",
    ),
)
def repair_matmul_raw(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    blocks: Optional[Tuple[int, int, int]] = None,
    out_dtype=None,
    detector=None,
) -> Tuple[jax.Array, jax.Array]:
    """c = repair(a) @ repair(b), plus event counters.  Register-mode core;
    ops.repair_matmul adds the reactive memory-mode write-back on top.

    ``detector`` (a ``core.rules.Detector``) picks the fatal-pattern set;
    its constants ride into the kernel as a scalar-prefetch operand."""
    if interpret is None:
        interpret = common.default_interpret()
    det = common.resolve_detector(detector, include_inf)
    (M, K), (K2, N) = a.shape, b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    if blocks is None:
        blocks = (_pick(M, 256), _pick(N, 256), _pick(K, 512))
    bm, bn, bk = blocks
    nk = K // bk
    grid = (M // bm, N // bn, nk)

    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # the detector-constants operand
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, c: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, c: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k, c: (i, j)),
            common.smem_spec(),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    c, counts = pl.pallas_call(
        functools.partial(
            _mm_kernel,
            policy=policy,
            constant=constant,
            nk=nk,
            out_dtype=out_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M, N), out_dtype),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        ],
        interpret=interpret,
    )(
        jnp.stack([
            common.detector_operand(det, a.dtype),
            common.detector_operand(det, b.dtype),
        ]),
        a, b,
    )
    return c, counts
