"""Shared in-kernel repair logic for all Pallas kernels.

The detection/repair math is *identical* to ``core.detect``/``core.policies``
(single source of truth for the bit patterns); this module re-expresses it in
a form usable inside a kernel body, where the loaded VMEM tile is a jax array
and the repair must be branch-free VPU code (compare/and/select — no gather,
no data-dependent shapes).

Policy support inside kernels is the *cheap* subset of the policy lattice:

  zero              repaired lanes become 0
  constant          repaired lanes become a compile-time constant
  neighbor_mean     repaired lanes become the mean of the finite lanes of the
                    SAME VMEM tile (one extra reduction over a tile already
                    resident in VMEM — this is the fused-repair trick: the
                    statistics come for free while the MXU is busy)
  clamp_finite_max  largest finite magnitude of the dtype

The expensive ``last_checkpoint`` policy is pytree-level only
(core/checkpoint_repair.py) — it needs a reference buffer the kernel does not
have.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import detect, rules as rules_lib

# Policies expressible inside a kernel body.
KERNEL_POLICIES = ("zero", "constant", "neighbor_mean", "clamp_finite_max")

# ---------------------------------------------------------------------------
# Detector constants (README §RepairRule).
#
# Detection inside a kernel is no longer baked-in NaN-only logic: the IEEE
# layout constants and the detector's enables travel as a small int32[8]
# scalar-prefetch operand (SMEM on TPU, available before the kernel body —
# layout documented on ``core.rules.Detector.constants``):
#
#   0 exp_mask   1 man_mask   2 flags   3 range exp-field threshold (shifted)
#   4 bitpattern mask   5 bitpattern value
#   6 count-valid row bound: when > 0, the scrub kernel masks folded-2D rows
#     ≥ this bound out of its lane COUNTS (the rows are still repaired) —
#     the page-scrub bucketing's padding-duplicate mask (``RepairPlan``)
#   7 pad
#
# so swapping the detector (NaN-only vs +Inf vs range-guarded vs a custom
# bit pattern) changes an operand, not the compiled kernel.
# ---------------------------------------------------------------------------

DEFAULT_DETECTOR = rules_lib.Detector()


def kernel_fill(fill) -> Optional[Tuple[str, float]]:
    """Map a ``RepairRule`` fill onto a kernel (policy, constant) pair that
    is *bit-identical* to the jnp repair path — value-independent fills
    only.  ``neighbor_mean`` (tile statistics differ between the kernels'
    VMEM tiles and the policy layer's fit) and the sign-preserving jnp
    ``clamp_finite_max`` have kernel analogues but not bit-equal ones, so
    they return ``None``: callers fall back to the jnp lowering rather than
    silently drift.  This is the ONE eligibility definition shared by the
    fused paged-decode path and the plan-level kernel placement."""
    if isinstance(fill, (int, float)) and not isinstance(fill, bool):
        return ("constant", float(fill))
    if fill == "zero":
        return ("zero", 0.0)
    from ..core import policies as policies_lib

    if isinstance(fill, policies_lib.RepairPolicy) and fill.name == "zero":
        return ("zero", 0.0)
    return None


def resolve_detector(
    detector: Optional[rules_lib.Detector], include_inf: bool
) -> rules_lib.Detector:
    """The effective kernel detector: an explicit one wins; otherwise the
    legacy ``include_inf`` knob lifts into the equivalent detector."""
    if detector is not None:
        return detector
    return rules_lib.Detector(nan=True, inf=include_inf)


def detector_operand(
    detector: rules_lib.Detector, dtype, n_valid_rows=None
) -> jax.Array:
    """The int32[8] scalar-prefetch operand encoding ``detector`` for
    ``dtype`` (see ``Detector.constants``).  ``n_valid_rows`` (traced or
    int) rides in slot 6 — the count-valid row bound; ``None``/0 disables
    the mask.  A traced bound stays a data change: same executable."""
    import numpy as np

    consts = detector.constants(dtype)
    # masks are bit patterns: fold into int32 range via two's complement
    base = jnp.asarray(np.asarray(consts, np.uint32).astype(np.int32))
    if n_valid_rows is None:
        return base
    return base.at[6].set(jnp.asarray(n_valid_rows, jnp.int32))


def consts_row(ref, row: Optional[int] = None) -> Tuple[jax.Array, ...]:
    """The eight detector constants of a scalar-prefetch ref (row ``row``
    of an int32[R, 8] operand, or the whole int32[8] one), loaded one
    scalar at a time: on TPU the operand lives in SMEM, which serves
    scalar loads only."""
    if row is None:
        return tuple(ref[i] for i in range(8))
    return tuple(ref[row, i] for i in range(8))


def zero_counts(ref, n: int) -> None:
    """Zero an int32[n] SMEM counter output one scalar store at a time."""
    for i in range(n):
        ref[i] = jnp.int32(0)


def masks_from_consts(
    bits: jax.Array, consts
) -> Tuple[jax.Array, jax.Array]:
    """(nan_mask, inf_mask) of a tile's integer bit view, driven by the
    detector constants (``consts_row`` scalars).  Mirrors
    ``Detector.masks`` exactly (same bucket rules, so kernel counters and
    the jnp oracle agree): custom bit patterns land in the NaN bucket; the
    range guard owns the non-NaN bucket when enabled (it subsumes ±Inf)."""
    u = lambda i: consts[i].astype(jnp.uint32)                       # noqa: E731
    b = bits.astype(jnp.uint32)
    exp_mask, man_mask, flags = u(0), u(1), consts[2]
    exp_all = (b & exp_mask) == exp_mask
    man_nz = (b & man_mask) != 0
    nan_m = exp_all & man_nz & ((flags & rules_lib.FLAG_NAN) > 0)
    nan_m = nan_m | (
        ((b & u(4)) == u(5)) & ((flags & rules_lib.FLAG_BITPATTERN) > 0)
    )
    inf_m = exp_all & ~man_nz & ((flags & rules_lib.FLAG_INF) > 0)
    ext_m = ((b & exp_mask) >= u(3)) & ((flags & rules_lib.FLAG_RANGE) > 0)
    inf_m = inf_m | (ext_m & ~nan_m)
    return nan_m, inf_m


def fatal_mask(tile: jax.Array, *, include_inf: bool = True) -> jax.Array:
    """NaN (optionally +±Inf) lanes of a VMEM tile, via bit patterns.

    Uses the same layout constants as core.detect so kernel and oracle agree
    bit-for-bit.  bitcast + compare + and: pure VPU ops.
    """
    bits = jax.lax.bitcast_convert_type(
        tile, detect.layout_of(tile.dtype).int_dtype
    )
    m = detect.is_nan_bits(bits, tile.dtype)
    if include_inf:
        m = m | detect.is_inf_bits(bits, tile.dtype)
    return m


def repair_value(
    tile: jax.Array, mask: jax.Array, policy: str, constant: float
) -> jax.Array:
    """Branch-free repair value for masked lanes (same shape as tile)."""
    if policy == "zero":
        return jnp.zeros_like(tile)
    if policy == "constant":
        return jnp.full_like(tile, constant)
    if policy == "clamp_finite_max":
        return jnp.full_like(tile, jnp.finfo(tile.dtype).max)
    if policy == "neighbor_mean":
        ok = ~mask
        # f32 accumulation of the tile statistics regardless of storage dtype
        okf = ok.astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(okf), 1.0)
        total = jnp.sum(jnp.where(ok, tile.astype(jnp.float32), 0.0))
        return jnp.broadcast_to(total / cnt, tile.shape).astype(tile.dtype)
    raise ValueError(f"kernel policy must be one of {KERNEL_POLICIES}, got {policy!r}")


def repair_tile(
    tile: jax.Array,
    *,
    policy: str,
    constant: float = 0.0,
    include_inf: bool = True,
    consts=None,
    count_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Repair a VMEM tile.  Returns (repaired, nan_count, inf_count) where the
    counts are int32 scalars for the event counters (Table 3 analogue).

    With ``consts`` (the detector constants, ``consts_row``) detection is
    data-driven — NaN/Inf/range/bit-pattern enables read from SMEM; the bare
    ``include_inf`` form keeps the legacy static NaN(+Inf) pattern.
    ``count_mask`` (bool, tile-shaped) restricts the COUNTS to its True
    lanes — repair always covers the whole tile (padding-duplicate rows
    must scatter identical repaired values to stay deterministic)."""
    bits = jax.lax.bitcast_convert_type(
        tile, detect.layout_of(tile.dtype).int_dtype
    )
    if consts is not None:
        nan_m, inf_m = masks_from_consts(bits, consts)
        mask = nan_m | inf_m
        fixed = jnp.where(
            mask, repair_value(tile, mask, policy, constant), tile
        )
        if count_mask is not None:
            nan_m = nan_m & count_mask
            inf_m = inf_m & count_mask
        return (
            fixed,
            jnp.sum(nan_m.astype(jnp.int32)),
            jnp.sum(inf_m.astype(jnp.int32)),
        )
    nan_m = detect.is_nan_bits(bits, tile.dtype)
    inf_m = detect.is_inf_bits(bits, tile.dtype)
    mask = (nan_m | inf_m) if include_inf else nan_m
    fixed = jnp.where(mask, repair_value(tile, mask, policy, constant), tile)
    return (
        fixed,
        jnp.sum(nan_m.astype(jnp.int32)),
        jnp.sum(inf_m.astype(jnp.int32)) if include_inf else jnp.zeros((), jnp.int32),
    )


@functools.lru_cache(maxsize=None)
def default_interpret() -> bool:
    """Run kernels in interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def smem_spec():
    """Whole-array SMEM block: the counter outputs every grid step
    accumulates into with scalar stores (VMEM takes no scalar stores)."""
    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    return pl.BlockSpec(memory_space=pltpu.SMEM)
