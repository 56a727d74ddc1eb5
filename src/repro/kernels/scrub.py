"""One-shot scrub kernel: in-place NaN/Inf repair over a whole buffer.

This is the *memory-repairing mechanism* (paper §3.4) as a standalone pass:
read each tile HBM→VMEM, repair fatal lanes, write the tile back, count
events.  It is used

  * by memory-mode pytree scrubs on the hot buffers (weights / KV cache /
    optimizer state) at step boundaries,
  * by checkpoint save/restore (never persist a NaN), and
  * as the honest "proactive / ECC-analogue" baseline in §Perf: calling it
    before every consuming op doubles HBM traffic, which is exactly the
    overhead the paper's reactive design avoids — the fused repair in
    repair_matmul.py / repair_attention.py costs zero extra HBM bytes.

Memory layout: the input is viewed as (rows, cols) with cols a multiple of
the 128-lane VPU width; tiles of (block_rows, 128·k).  The write-back aliases
the input buffer (``input_output_aliases``), so on TPU the scrub is in-place
in HBM, exactly like the paper's repair of the faulting address.

Outputs: (scrubbed, counts) with counts = int32[3] = [nan, inf, events]
accumulated across all grid steps (a whole-array SMEM output — every grid
step adds to the same three scalars, written back once at the end).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import tiling
from . import common


def _scrub_kernel(
    consts_ref, x_ref, out_ref, counts_ref, *, policy: str, constant: float
):
    # consts_ref is the scalar-prefetch detector-constants operand (int32[8],
    # SMEM): detection enables/masks are data, not baked-in NaN-only logic.
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        common.zero_counts(counts_ref, 3)

    tile = x_ref[...]
    consts = common.consts_row(consts_ref)
    # consts[6] > 0: count-valid row bound — rows ≥ bound (the page scrub's
    # padding duplicates) are repaired like any other but masked out of the
    # lane counts, so padded and unpadded calls report identical stats
    n_valid = consts[6]
    row_ids = pl.program_id(0) * tile.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, tile.shape, 0
    )
    count_mask = (n_valid == 0) | (row_ids < n_valid)
    fixed, n_nan, n_inf = common.repair_tile(
        tile, policy=policy, constant=constant, consts=consts,
        count_mask=count_mask,
    )
    out_ref[...] = fixed
    event = ((n_nan + n_inf) > 0).astype(jnp.int32)
    counts_ref[0] += n_nan
    counts_ref[1] += n_inf
    counts_ref[2] += event


def _choose_blocks(rows: int, cols: int) -> Tuple[int, int]:
    """Pick VMEM-friendly tile sizes: lane dim a multiple of 128 (≤512),
    sublane dim a multiple of 8 (≤256), clamped to the array — the shared
    fit from ``core.tiling`` (also the neighbor_mean policy's tile)."""
    return tiling.fit_blocks(rows, cols)


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "constant", "include_inf", "interpret", "block", "detector",
    ),
)
def scrub(
    x: jax.Array,
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    block: Optional[Tuple[int, int]] = None,
    detector=None,
    n_valid_rows=None,
) -> Tuple[jax.Array, jax.Array]:
    """Repair all fatal lanes of ``x`` in place.  Returns (scrubbed, counts).

    counts = int32[3]: [nan lanes, inf lanes, tile-visits with ≥1 fatal lane].
    Arbitrary-rank inputs are viewed as 2D (leading dims folded into rows).

    ``detector`` (a ``core.rules.Detector``) selects which stored patterns
    are fatal; its constants enter the kernel as a scalar-prefetch operand
    (README §RepairRule).  Default: the legacy NaN(+Inf) pattern via
    ``include_inf``.

    ``n_valid_rows`` (traced int32 or None) bounds the lane COUNTS to the
    first that many folded-2D rows — every row is still repaired.  This is
    how bucketed page scrubs (``scrub_pages``) keep padding duplicates out
    of their stats; it rides the scalar-prefetch operand (slot 6), so a
    changing bound never retraces.
    """
    if interpret is None:
        interpret = common.default_interpret()
    det = common.resolve_detector(detector, include_inf)
    orig_shape = x.shape
    if x.ndim == 0:
        x2 = x.reshape(1, 1)
    elif x.ndim == 1:
        x2 = x.reshape(1, -1)
    else:
        x2 = x.reshape(-1, x.shape[-1])
    rows, cols = x2.shape
    br, bc = block if block is not None else _choose_blocks(rows, cols)
    grid = (rows // br, cols // bc)

    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # the detector-constants operand
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j, c: (i, j))],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j, c: (i, j)),
            common.smem_spec(),
        ],
    )
    out, counts = pl.pallas_call(
        functools.partial(_scrub_kernel, policy=policy, constant=constant),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x2.dtype),
            jax.ShapeDtypeStruct((3,), jnp.int32),
        ],
        # operand 0 is the scalar prefetch; x is operand 1 — aliased onto the
        # scrubbed output: in-place in HBM, like the paper
        input_output_aliases={1: 0},
        interpret=interpret,
    )(common.detector_operand(det, x2.dtype, n_valid_rows), x2)
    return out.reshape(orig_shape), counts


def scrub_sharded(
    x: jax.Array,
    mesh,
    spec,
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    block: Optional[Tuple[int, int]] = None,
    detector=None,
) -> Tuple[jax.Array, jax.Array]:
    """Shard-local scrub entry (README §Distributed repair): run the Pallas
    scrub kernel over each device's *local shard view* via shard_map — no
    gather, no resharding; every device repairs exactly the rows it holds,
    which is the placement the ``RepairPlan`` "sharded" path lowers to.

    ``spec`` is the PartitionSpec of ``x`` on ``mesh``.  Returns
    ``(scrubbed, counts)`` with the same int32[3] counts as ``scrub``,
    psum-reduced to GLOBAL totals (counted once, never per-replica).  NaN
    and Inf lane counts match the whole-array kernel exactly; the
    tile-visit ``events`` entry follows the per-shard tiling (a shard's
    tiles, not the global array's), the same way the fused kernels' event
    counts follow their block shapes.
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = common.default_interpret()

    # reduce ONLY over the mesh axes the spec actually shards: along unused
    # axes every replica computes identical local counts, and psum-ing those
    # would multiply the global totals by the replication factor
    used = []
    for part in spec:
        if part is None:
            continue
        used.extend(part if isinstance(part, (tuple, list)) else (part,))
    used = tuple(a for a in used if a is not None)

    def local(xs: jax.Array) -> Tuple[jax.Array, jax.Array]:
        fixed, counts = scrub(
            xs, policy=policy, constant=constant, include_inf=include_inf,
            interpret=interpret, block=block, detector=detector,
        )
        if used:
            counts = jax.lax.psum(counts, axis_name=used)
        return fixed, counts

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec,), out_specs=(spec, P()),
        check_vma=False,
    )(x)


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "constant", "include_inf", "interpret", "block", "detector",
    ),
)
def scrub_pages(
    x: jax.Array,
    page_ids: jax.Array,
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    block: Optional[Tuple[int, int]] = None,
    detector=None,
    n_valid=None,
) -> Tuple[jax.Array, jax.Array]:
    """Page-view scrub: repair only rows ``page_ids`` of ``x``'s leading
    (page) axis.  Gather the pages into one contiguous view, run the scrub
    kernel over that view, scatter the repaired pages back.  HBM traffic is
    proportional to the *scrubbed* pages, not the whole buffer.

    This is the kernel-level counterpart of the serving engine's
    page-granular repair — ``RepairPlan`` lowers pages-scope scrubs through
    it wherever the kernels are native (README §RepairPlan), with the same
    bucketed id vector the jnp path uses: ``n_valid`` (traced int32 or
    None) marks entries ``page_ids[n_valid:]`` as padding duplicates whose
    lanes are repaired but masked out of the counts (they gather to the
    trailing folded rows, so the bound lowers to ``scrub``'s
    ``n_valid_rows`` rider — slot 6 of the scalar operand, never a
    retrace).  1-D ``x`` cannot express a row bound (one page = part of one
    folded row); callers needing masked counts there keep the jnp path.

    Returns ``(x', counts)`` with the same int32[3] counts as ``scrub``.
    Without ``n_valid``, duplicate page ids are idempotent (the repaired
    rows coincide) but inflate the lane counts — pass unique ids when
    counts matter.
    """
    page_ids = jnp.asarray(page_ids, jnp.int32)
    rows = x[page_ids]
    n_valid_rows = None
    if n_valid is not None and rows.ndim >= 2:
        rows_per_page = rows[0].size // rows.shape[-1]
        n_valid_rows = jnp.asarray(n_valid, jnp.int32) * rows_per_page
    fixed, counts = scrub(
        rows, policy=policy, constant=constant, include_inf=include_inf,
        interpret=interpret, block=block, detector=detector,
        n_valid_rows=n_valid_rows,
    )
    return x.at[page_ids].set(fixed), counts
