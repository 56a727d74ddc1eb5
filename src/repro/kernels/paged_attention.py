"""Paged decode attention with fused on-read repair — the trap, in the read.

This is the serving engine's decode hot path run *straight off the pool*:
the kernel consumes the pool's page-major KV leaves plus per-request block
tables (the layout vLLM's PagedAttention popularized), so the engine never
gathers pages into a contiguous per-step view and never scatters one back.
The per-step full-KV copy — the #1 ROADMAP open item after PR 3 — is gone;
the page-axis sharding of the pool finally pays off end to end, and (per
EDEN) the approximate data stays in place instead of round-tripping.

Repair semantics are the truest realization of the paper's trap-on-read
design this repo has: each (page, layer) row is bit-pattern checked and
repaired in VMEM right after the HBM→VMEM DMA the attention performs
anyway — detection and repair fused into the read, zero extra HBM traffic —
and the kernel emits *per-page-slot fatal counts*, so the reactive repair
manager knows exactly which resident pages hold a fatal lane without any
separate detection scan over the pages the step touched.

Layout:

  q             (B, H, Dh)          one query token per decode slot
  k/v pages     (P, L, pg, Kh, Dh)  the pool leaves, page axis LEADING
                                    (``Model.paged_cache_defs``); ``layer``
                                    selects the L row via scalar prefetch
  block_tables  (B, M) int32        per-request page lists, null-padded
  positions     (B) int32           last valid context position (inclusive)

Two decode walks share that contract.  A block-table slot ``j`` is *live*
when its first position ``j * pg`` lies within the request's context
(positions ``0..pos``); the slots past it are null padding or pages
allocated ahead.  The chunked prefill (``paged_prefill_raw``) walks its
live pages the split-K way, up to the chunk's last row.

* Split-K (``paged_attention_splitk_raw``, the serving path once the table
  is 8 pages wide): grid (B, splits); each cell copies only its live
  pages from HBM, several pages per double-buffered block, and stops at
  its last live block.  Null padding and the unallocated tail are never
  fetched, so a flip there cannot reach a context and nothing detects it.
* Serial (``paged_attention_raw``): grid (B, M), one physical page per
  inner step, its pool row selected *by the block table* through the k/v
  BlockSpec index maps (a scalar-prefetch operand, available before the
  kernel body).  Every slot is streamed and repaired in VMEM — a NaN
  parked in the null page would otherwise poison the context through
  ``0 * NaN`` in the value contraction — but only live slots count.

Outputs: (out (B, H, Dh), slot_counts (B, M) int32, counts int32[8]).
``slot_counts[b, j]`` is the fatal-lane count of the page read at live
block slot (b, j), and 0 at every slot past the context — scatter-added
over the block table this becomes the ``(n_pages,)`` per-page vector the
serving repair manager consumes (pages read by several slots accumulate
per read; the manager only needs the >0 predicate).  ``counts`` is the
shared AT_* event layout of ``repair_attention`` so the unified stats
routing is identical.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import common

NEG_INF = -1e30

# counts layout (int32[8]) — identical to repair_attention's AT_* layout
NAN_K, INF_K, EV_K, NAN_V, INF_V, EV_V, EV_TOTAL = range(7)

# sentinel default for the detector kwargs: "the legacy NaN(+Inf) pattern
# via include_inf".  ``None`` is a *meaningful* value (detection disabled
# for that operand), so the default cannot be None.
DEFAULT_DETECTOR = "default"

# scoped VMEM of the chunked-q prefill kernel, at least: its score tiles
# overflow the default scoped limit once the kernel runs under shard_map.  A
# grid cell with more query rows asks for more
_PREFILL_VMEM_BYTES = 32 << 20

# query rows (chunk rows x query heads) of one prefill grid cell, at most: a
# 256-row chunk at 12 heads fits whole; at 48 heads it takes four row tiles.
# A tile's rows per KV head fill whole 128-lane rows, so a head group whose
# 128-lane unit alone holds more rows (32 heads, no sharing) takes that unit
_PREFILL_TILE_ROWS = 3072

# per-slot chunk-start sentinel for the sharded prefill walk: a slot whose
# q_start carries this value belongs to another device's shard — no chunk
# row reaches it, so it is never read nor counted
NO_SLOT = -(1 << 30)

# keys per block of the live page walks (split-K decode, chunked prefill):
# one lane-dense score tile of 2 x 128 lanes, so a 16-token page pool walks
# up to 16 pages per copy block
_BLOCK_KEYS = 256


def _record_counts(
    slot_ref, counts_ref, slot, nan_k, inf_k, nan_v, inf_v, gate
):
    """Accumulate one page's AT_* events and write its per-page-slot fatal
    count (``gate`` 0 masks both).  The counters and the slot counts live
    in SMEM, so every access is one scalar load or store."""
    ev_k = ((nan_k + inf_k) > 0).astype(jnp.int32)
    ev_v = ((nan_v + inf_v) > 0).astype(jnp.int32)
    counts_ref[NAN_K] += gate * nan_k
    counts_ref[INF_K] += gate * inf_k
    counts_ref[EV_K] += gate * ev_k
    counts_ref[NAN_V] += gate * nan_v
    counts_ref[INF_V] += gate * inf_v
    counts_ref[EV_V] += gate * ev_v
    counts_ref[EV_TOTAL] += gate * ((ev_k + ev_v) > 0).astype(jnp.int32)
    slot_ref[slot] = gate * (nan_k + inf_k + nan_v + inf_v)


def _repair_and_count(
    consts_ref, k_ref, v_ref, slot_ref, counts_ref, slot,
    *, policy_k: str, constant_k: float, policy_v: str, constant_v: float,
    gate=None,
):
    """Fused on-read repair of one page's K/V rows (the trap) in the serial
    decode kernel.  Per-operand fill selection:
    each tile repairs with ITS operand's rule fill (row 0 = K, row 1 = V),
    so a mixed-fill RuleSet compiles into one kernel instead of forcing the
    gathered fallback.  Accumulates the AT_* event counts and writes the
    per-page-slot fatal count the reactive repair manager consumes.

    ``gate`` (int32 0/1, default 1) masks the *counting* side only: a slot
    past the request's context is still streamed and repaired here
    (harmless — its scores are fully masked) but reports nothing, so a page
    is counted only where a context reads it.

    ``slot`` is the ``(b, j)`` block-table slot this grid step visits."""
    if gate is None:
        gate = jnp.int32(1)
    k_fixed, nan_k, inf_k = common.repair_tile(
        k_ref[0, 0], policy=policy_k, constant=constant_k,
        consts=common.consts_row(consts_ref, 0),
    )
    v_fixed, nan_v, inf_v = common.repair_tile(
        v_ref[0, 0], policy=policy_v, constant=constant_v,
        consts=common.consts_row(consts_ref, 1),
    )
    _record_counts(
        slot_ref, counts_ref, slot, nan_k, inf_k, nan_v, inf_v, gate
    )
    return k_fixed, v_fixed


def _detector_consts(detector_k, detector_v, dtype, include_inf: bool):
    """The int32[2, 8] scalar-prefetch constants (row 0 = K, row 1 = V)
    shared by every kernel in the paged family."""

    def operand_row(det):
        if det is None:
            # all detection flags off: the kernel loads, never repairs
            return jnp.zeros((8,), jnp.int32)
        if det == DEFAULT_DETECTOR:
            det = common.resolve_detector(None, include_inf)
        return common.detector_operand(det, dtype)

    return jnp.stack([operand_row(detector_k), operand_row(detector_v)])


def _lse_merge(out_dtype, o_part, m_part, l_part):
    """Log-sum-exp merge of unnormalized partials along axis 1 — the
    reduce stage shared by split-K flash decoding (partials = splits) and
    the sharded walk (partials = devices × splits).  Partials whose slice
    was pure null padding / not owned carry ``m = -inf``: their exp()
    weight is forced to zero rather than trusting exp(-inf - m*)
    arithmetic, which would turn into exp(0) = 1 when every partial of a
    row is empty."""
    m_star = jnp.max(m_part, axis=1)                         # (B, H)
    live = m_part > NEG_INF * 0.5                            # (B, S, H)
    w = jnp.where(live, jnp.exp(m_part - m_star[:, None, :]), 0.0)
    l_tot = jnp.sum(w * l_part, axis=1)                      # (B, H)
    acc = jnp.sum(w[..., None] * o_part, axis=1)             # (B, H, Dh)
    return (acc / jnp.maximum(l_tot, 1e-30)[..., None]).astype(out_dtype)


def _paged_kernel(
    consts_ref,      # int32[2, 8]  detector constants: row 0 K, row 1 V
    bt_ref,          # int32[B, M]  block tables (also drives the index maps)
    pos_ref,         # int32[B]     last valid position per request
    layer_ref,       # int32[1]     which L row of the pool leaves
    q_ref, k_ref, v_ref,
    o_ref, slot_ref, counts_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale: float,
    policy_k: str, constant_k: float, policy_v: str, constant_v: float,
    pg: int, n_kv: int, group: int, nm: int, out_dtype,
):
    b, j = pl.program_id(0), pl.program_id(1)
    step = b * pl.num_programs(1) + j

    @pl.when(step == 0)
    def _init_counts():
        common.zero_counts(counts_ref, 8)

    @pl.when(j == 0)
    def _init_state():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # slots past the context are streamed (the walk is M wide) but count
    # nothing: the split-K walk never reads them, and both report alike
    k_fixed, v_fixed = _repair_and_count(
        consts_ref, k_ref, v_ref, slot_ref, counts_ref, (b, j),
        policy_k=policy_k, constant_k=constant_k,
        policy_v=policy_v, constant_v=constant_v,
        gate=(j * pg <= pos_ref[b]).astype(jnp.int32),
    )

    # ---- online softmax over this page ----
    H = n_kv * group
    q = q_ref[0].astype(jnp.float32).reshape(n_kv, group, q_ref.shape[-1])
    kb = jnp.moveaxis(k_fixed.astype(jnp.float32), 1, 0)     # (Kh, pg, Dh)
    s = jax.lax.dot_general(
        q, kb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * sm_scale                                             # (Kh, G, pg)
    t = j * pg + jax.lax.broadcasted_iota(jnp.int32, (1, 1, pg), 2)
    s = jnp.where(t <= pos_ref[b], s, NEG_INF)
    s2 = s.reshape(H, pg)

    m_prev = m_ref[:, 0]                                     # (H,)
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1))
    p = jnp.exp(s2 - m_new[:, None])                         # (H, pg)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
    # softmax weights quantize to the cache dtype before the value
    # contraction — the gathered decode's `w.astype(cv.dtype)` and the
    # flash kernel's `p.astype(v_blk.dtype)`, kept here so the fused path
    # matches the gathered one (bit-exact for f32 pools; for bf16 the
    # online-softmax alpha-rescale happens after quantization, so parity
    # is approximate at the value level, token-level in practice)
    vb = jnp.moveaxis(v_fixed, 1, 0)                         # (Kh, pg, Dh)
    pv = jax.lax.dot_general(
        p.reshape(n_kv, group, pg).astype(v_fixed.dtype), vb,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                        # (Kh, G, Dh)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv.reshape(acc_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nm - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "constant", "include_inf", "interpret",
        "detector_k", "detector_v",
        "policy_k", "constant_k", "policy_v", "constant_v",
    ),
)
def paged_attention_raw(
    q: jax.Array,              # (B, H, Dh)
    k_pages: jax.Array,        # (P, L, pg, Kh, Dh)
    v_pages: jax.Array,        # (P, L, pg, Kh, Dh)
    block_tables: jax.Array,   # (B, M) int32
    positions: jax.Array,      # (B,) int32, inclusive
    layer: jax.Array,          # int32 scalar — L row of the pool leaves
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR,
    detector_v=DEFAULT_DETECTOR,
    policy_k: Optional[str] = None,
    constant_k: Optional[float] = None,
    policy_v: Optional[str] = None,
    constant_v: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of paged decode attention with fused on-read repair.

    ``detector_k`` / ``detector_v`` pick the fatal-pattern set per operand:
    a ``core.rules.Detector``, the default sentinel (legacy NaN(+Inf) via
    ``include_inf``), or ``None`` — detection disabled for that operand
    entirely (a zeroed-flags constants row; the exact-region /
    non-reactive-rule case), which keeps the read bit-transparent.
    ``policy_k``/``constant_k`` and ``policy_v``/``constant_v`` pick the
    fill per operand the same way (``None`` inherits the shared
    ``policy``/``constant``) — a mixed-fill RuleSet compiles into ONE
    kernel, each tile repairing with its operand's own fill.  Returns
    ``(out (B, H, Dh), slot_counts (B, M) int32, counts int32[8])``.
    """
    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B, H, Dh = q.shape
    P, L, pg, Kh, _ = k_pages.shape
    assert v_pages.shape == k_pages.shape, (k_pages.shape, v_pages.shape)
    assert H % Kh == 0, (H, Kh)
    group = H // Kh
    M = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(Dh)
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)

    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,    # detector consts, block tables, positions, layer
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, j, c, bt, pos, lay: (b, 0, 0)),
            # the block table IS the index map: page (b, j) of the request's
            # table selects the pool row — no gather ever materializes
            pl.BlockSpec(
                (1, 1, pg, Kh, Dh),
                lambda b, j, c, bt, pos, lay: (bt[b, j], lay[0], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, pg, Kh, Dh),
                lambda b, j, c, bt, pos, lay: (bt[b, j], lay[0], 0, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, j, c, bt, pos, lay: (b, 0, 0)),
            common.smem_spec(),     # slot counts (B, M)
            common.smem_spec(),     # counts int32[8]
        ],
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    out, slot_counts, counts = pl.pallas_call(
        functools.partial(
            _paged_kernel,
            sm_scale=sm_scale,
            policy_k=policy_k,
            constant_k=constant_k,
            policy_v=policy_v,
            constant_v=constant_v,
            pg=pg,
            n_kv=Kh,
            group=group,
            nm=M,
            out_dtype=q.dtype,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, M), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        ],
        interpret=interpret,
    )(
        consts,
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(positions, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q, k_pages, v_pages,
    )
    return out, slot_counts, counts


def paged_attention(
    q: jax.Array,              # (B, H, Dh)
    k_pages: jax.Array,        # (P, pg, Kh, Dh) or (P, L, pg, Kh, Dh)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, M) int32
    positions: jax.Array,      # (B,) int32, inclusive
    *,
    layer: int = 0,
    **kw,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Convenience entry: accepts layer-free ``(P, pg, Kh, Dh)`` pools (the
    single-layer tests/bench shape) and returns ``(out, page_counts,
    counts)`` with ``page_counts`` already scatter-added to the pool's page
    axis — the ``(n_pages,)`` per-page fatal vector."""
    if k_pages.ndim == 4:
        k_pages = k_pages[:, None]
        v_pages = v_pages[:, None]
    out, slot_counts, counts = paged_attention_raw(
        q, k_pages, v_pages, block_tables, positions,
        jnp.asarray(layer, jnp.int32), **kw,
    )
    page_counts = jnp.zeros((k_pages.shape[0],), jnp.int32).at[
        jnp.asarray(block_tables, jnp.int32)
    ].add(slot_counts)
    return out, page_counts, counts


# --------------------------------------------------------------------------
# The live page walk shared by chunked prefill and split-K decode: a grid
# cell copies only its live slots' pages from the HBM pool leaves, several
# pages per double-buffered block, reads each block as dense 32-bit words
# and repairs it in VMEM.
# --------------------------------------------------------------------------
def _block_pages(ns: int, pg: int) -> int:
    """Pages per block of a live walk over ``ns`` block-table slots: the
    largest divisor of ``ns`` whose keys fill at most one lane-dense score
    tile of ``_BLOCK_KEYS``."""
    fit = [d for d in range(1, ns + 1)
           if ns % d == 0 and d * pg <= _BLOCK_KEYS]
    return max(fit, default=1)


def _live_blocks(live, bp: int):
    """Trip counts of a live walk: ``live`` (..., ns) bool per slot gives
    (...) int32, the number of ``bp``-slot blocks up to and including the
    last block that holds a live slot."""
    *lead, ns = live.shape
    nb = ns // bp
    idx = jnp.arange(1, nb + 1, dtype=jnp.int32)
    any_live = live.reshape(*lead, nb, bp).any(-1)
    return jnp.max(jnp.where(any_live, idx, 0), axis=-1).astype(jnp.int32)


def _fill_bits(policy: str, constant: float, dtype) -> int:
    """The bit pattern a value-independent fill writes into a repaired lane
    of ``dtype`` — the live walk repairs whole blocks of raw words."""
    value = {
        "zero": 0.0,
        "constant": constant,
        "clamp_finite_max": float(jnp.finfo(dtype).max),
    }.get(policy)
    if value is None:
        raise ValueError(
            f"the live page walk fills with zero, constant or "
            f"clamp_finite_max, not {policy!r}"
        )
    bits = np.asarray(value, jnp.dtype(dtype))
    return int(bits.view(np.uint16 if bits.itemsize == 2 else np.uint32))


def _check_walk_pool(k_pages, v_pages):
    assert v_pages.shape == k_pages.shape, (k_pages.shape, v_pages.shape)
    assert k_pages.dtype in (jnp.float32, jnp.bfloat16), (
        f"the live page walk reads float32 or bfloat16 pools, "
        f"not {k_pages.dtype}"
    )
    Kh = k_pages.shape[3]
    assert Kh % (4 // k_pages.dtype.itemsize) == 0, (
        f"a 16-bit pool packs KV heads in pairs: Kh={Kh} must be even"
    )


def _repair_heads(w, consts, fill: int, width: int):
    """The trap on a block of raw 32-bit words ``w``: each ``width``-bit
    lane is detected and, where fatal, replaced by the ``fill`` bits.  A
    16-bit pool packs two KV heads per word (the lower head in the low
    half); each half is moved to the high half of a word, where its
    bfloat16 bits are the float32 of the same value, and detected there
    with the detector constants shifted alike.  Returns, per head in the
    words, ``(value f32, NaN-bucket mask, non-NaN-bucket mask)``
    (``common.masks_from_consts``)."""
    if width == 32:
        halves, shift = [w], 0
    else:
        halves, shift = [w << 16, w & jnp.uint32(0xFFFF0000)], 16
    # masks, range threshold and bit pattern move with the lane; the
    # flags (2) and the row bound (6, unused here) do not
    consts = tuple(
        c << shift if k in (0, 1, 3, 4, 5) else c for k, c in enumerate(consts)
    )
    out = []
    for x in halves:
        nan_m, inf_m = common.masks_from_consts(x, consts)
        fixed = jnp.where(nan_m | inf_m, jnp.uint32(fill << shift), x)
        out.append(
            (jax.lax.bitcast_convert_type(fixed, jnp.float32), nan_m, inf_m)
        )
    return out


def _block_heads(ref, buf, consts, fill: int, n_keys: int):
    """One operand's block of ``n_keys`` keys in buffer ``buf``, repaired
    in VMEM: ``(value f32 (n_keys, Dh), nan, inf)`` per KV head.  The block
    is read as dense 32-bit words — a 16-bit pool's (Kh, Dh) page rows pair
    heads 2r, 2r+1 in one word — so each load fills whole vregs where
    (Kh, Dh) tiles would pad to the sublane count."""
    n_kv, Dh = ref.shape[-2:]
    width = jnp.dtype(ref.dtype).itemsize * 8                # 16 or 32
    stride = n_kv // (32 // width)       # word rows per key position
    wref = ref.at[buf].bitcast(jnp.uint32).reshape(n_keys * stride, Dh)
    out = []
    for r in range(stride):
        w = wref[pl.ds(r, n_keys, stride=stride)] if stride > 1 else wref[...]
        out += _repair_heads(w, consts, fill, width)
    return out


def _zero_slots(slot_ref, b, base: int | jax.Array, ns: int):
    """Zero slot counts ``(b, base .. base + ns)``: slots a walk never
    reads report 0, read pages overwrite theirs (a few scalar stores per
    loop step)."""
    u = max(d for d in range(1, 9) if ns % d == 0)

    def zero(t, carry):
        for k in range(u):
            slot_ref[b, base + t * u + k] = jnp.int32(0)
        return carry

    jax.lax.fori_loop(0, ns // u, zero, 0)


def _count_block(slot_ref, counts_ref, k_heads, v_heads, *, b, j0, pg, live):
    """A block's per-page fatal counts at their exact ``(b, slot)``, on the
    rare path where a lane fired anywhere in it; ``live(j)`` gates each
    page's count."""
    fatal = functools.reduce(
        jnp.logical_or, [n | f for _, n, f in k_heads + v_heads]
    )
    n_keys = fatal.shape[0]

    @pl.when(jnp.sum(fatal.astype(jnp.int32)) > 0)
    def _account():
        def page(hs, p, kind):
            return sum(
                jnp.sum(h[kind][p * pg:(p + 1) * pg].astype(jnp.int32))
                for h in hs
            )

        for p in range(n_keys // pg):
            _record_counts(
                slot_ref, counts_ref, (b, j0 + p),
                page(k_heads, p, 1), page(k_heads, p, 2),
                page(v_heads, p, 1), page(v_heads, p, 2),
                live(j0 + p).astype(jnp.int32),
            )


def _walk_live_blocks(
    n_blocks, live, attend, *, bt_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
    b, base, lay, bp: int,
):
    """Walk ``n_blocks`` blocks of ``bp`` block-table slots from ``base``:
    each block's live pages (``live(j)``) are copied from the HBM pool
    leaves into one half of the double-buffered ``k_buf``/``v_buf`` while
    ``attend(i, buf)`` reads the other.  A dead slot is not fetched: its
    buffer page is zeroed instead, so stale bits never meet the value
    contraction."""
    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    def copies(i, buf, p):
        page = bt_ref[b, base + i * bp + p]
        return (
            pltpu.make_async_copy(
                k_hbm.at[page, lay], k_buf.at[buf, p], sems.at[0, buf]
            ),
            pltpu.make_async_copy(
                v_hbm.at[page, lay], v_buf.at[buf, p], sems.at[1, buf]
            ),
        )

    def each_copy(i, buf, op, dead=None):
        """``op`` ("start" or "wait") on the K and V copies of each live
        page of block i — one branch when the whole block is live, else
        one per slot, with ``dead(p)`` on the rest."""
        slots = [base + i * bp + p for p in range(bp)]
        full = functools.reduce(jnp.logical_and, [live(j) for j in slots])

        def act(p):
            for cp in copies(i, buf, p):
                getattr(cp, op)()

        @pl.when(full)
        def _all():
            for p in range(bp):
                act(p)

        @pl.when(jnp.logical_not(full))
        def _some():
            for p, j in enumerate(slots):
                pl.when(live(j))(functools.partial(act, p))
                if dead is not None:
                    pl.when(jnp.logical_not(live(j)))(
                        functools.partial(dead, p)
                    )

    def start(i, buf):
        def clear(p):
            k_buf[buf, p] = jnp.zeros(k_buf.shape[2:], k_buf.dtype)
            v_buf[buf, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

        each_copy(i, buf, "start", clear)

    @pl.when(n_blocks > 0)
    def _prime():
        start(0, 0)

    def body(i, carry):
        buf = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start(i + 1, 1 - buf)

        each_copy(i, buf, "wait")
        attend(i, buf)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)


def _walk_scratch(bp: int, k_pages, v_pages):
    """The double-buffered block copies and their semaphores."""
    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    _, _, pg, Kh, Dh = k_pages.shape
    return [
        pltpu.VMEM((2, bp, pg, Kh, Dh), k_pages.dtype),
        pltpu.VMEM((2, bp, pg, Kh, Dh), v_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),     # (K|V, buffer)
    ]


# --------------------------------------------------------------------------
# Chunked-q paged prefill: admission attends straight off the pool too.
# --------------------------------------------------------------------------
def _paged_prefill_kernel(
    consts_ref,      # int32[2, 8]      detector constants: row 0 K, row 1 V
    bt_ref,          # int32[B, M]      block tables (page ids of the copies)
    qstart_ref,      # int32[B, M]      chunk-row-0 position, per block slot
    nblk_ref,        # int32[B, T]      live blocks to walk, per row tile
    layer_ref,       # int32[1]         which L row of the pool leaves
    q_ref, k_hbm, v_hbm,
    o_ref, mo_ref, lo_ref, slot_ref, counts_ref,
    k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
    *, sm_scale: float, fill_k: int, fill_v: int,
    pg: int, n_kv: int, group: int, nc: int, ct: int, bp: int,
):
    b, t = pl.program_id(0), pl.program_id(1)
    M = bt_ref.shape[1]
    R = ct * group                       # query rows per KV head in a tile
    N = bp * pg
    # the tile's last real row, from q_start: rows padded past the chunk
    # (the last tile's, when C is not a whole number of tiles) widen no walk
    horizon = jnp.minimum((t + 1) * ct, nc) - 1
    # the last tile's horizon is the chunk's last row: it reads every page
    # any tile reads, so it alone counts them — each page counted once
    counting = t == pl.num_programs(1) - 1

    @pl.when((b == 0) & (t == 0))
    def _init_counts():
        common.zero_counts(counts_ref, 8)

    @pl.when(counting)
    def _init_slots():
        _zero_slots(slot_ref, b, 0, M)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def live(j):
        # per-SLOT chunk start: on a single device every slot of request b
        # carries q_start[b]; under the sharded walk non-owned slots carry
        # NO_SLOT, which no row reaches
        return j * pg <= qstart_ref[b, j] + horizon

    rows = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)

    def attend(i, buf):
        j0 = i * bp
        k_heads = _block_heads(
            k_buf, buf, common.consts_row(consts_ref, 0), fill_k, N
        )
        v_heads = _block_heads(
            v_buf, buf, common.consts_row(consts_ref, 1), fill_v, N
        )
        pl.when(counting)(functools.partial(
            _count_block, slot_ref, counts_ref, k_heads, v_heads,
            b=b, j0=j0, pg=pg, live=live,
        ))

        # causal mask: tile row r (ordered chunk row, then query head of
        # the group) holds chunk row t * ct + r // group and reads a key
        # whose position less its slot's q_start is at most that row, i.e.
        # when r >= (key - q_start - t * ct) * group.  Keys past the chunk
        # (NO_SLOT slots among them) clamp to row C, which only the rows
        # padded past the chunk reach.
        qs = jnp.full((1, N), qstart_ref[b, j0], jnp.int32)
        for p in range(1, bp):
            qs = jnp.where(lane >= p * pg, qstart_ref[b, j0 + p], qs)
        rel = jnp.minimum(j0 * pg + lane - qs, nc)
        keep = rows >= (rel - t * ct) * group                # (R, N)

        for h in range(n_kv):
            # the wrapper hands q over in the contraction dtype: a bf16
            # pool and query contract in bf16 with f32 accumulation (bf16
            # products are exact in f32), anything else in f32
            s = jax.lax.dot_general(
                q_ref[0, h], k_heads[h][0].astype(q_ref.dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale                                     # (R, N)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # empty-walk guard: a row with no readable key yet (the sharded
            # walk's non-owned slots) keeps (m, l, acc) = (-inf, 0, 0)
            # exactly — against a max of -inf, exp(s - m) would be 1 per
            # masked lane
            p_ = jnp.exp(s - jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0))
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_ref[h][:, :1] * alpha + jnp.sum(p_, -1, keepdims=True)
            # softmax weights quantize to the cache dtype before the value
            # contraction, as in the decode walks and the gathered path
            pv = jax.lax.dot_general(
                p_.astype(v_buf.dtype), v_heads[h][0].astype(v_buf.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                # (R, Dh)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    _walk_live_blocks(
        nblk_ref[b, t], live, attend,
        bt_ref=bt_ref, k_hbm=k_hbm, v_hbm=v_hbm, k_buf=k_buf, v_buf=v_buf,
        sems=sems, b=b, base=0, lay=layer_ref[0], bp=bp,
    )

    # raw partials — normalization happens in the caller / LSE merge
    o_ref[0] = acc_ref[...]
    for h in range(n_kv):
        mo_ref[0, h, 0] = m_ref[h, :, 0]
        lo_ref[0, h, 0] = l_ref[h, :, 0]


def _prefill_row_tile(C: int, H: int, group: int) -> int:
    """Chunk rows per grid cell of the prefill walk: the whole chunk when
    its ``C * H`` query rows fit in ``_PREFILL_TILE_ROWS``, else the most
    chunk rows that do whose rows per KV head fill whole 128-lane rows (the
    m and l outputs are blocked by them), at least one such unit.  The
    chunk is padded to whole tiles."""
    if C * H <= _PREFILL_TILE_ROWS:
        return C
    unit = 128 // math.gcd(group, 128)
    return unit * max(1, _PREFILL_TILE_ROWS // (unit * H))


def _prefill_vmem_bytes(rows: int, Dh: int, q_itemsize: int) -> int:
    """Scoped VMEM for one grid cell of the chunked prefill, which holds
    ``rows`` query rows (a row tile's chunk rows x query heads): twice the
    bytes its blocks and scratch hold (the compiler's score tiles and
    temporaries come on top), never below ``_PREFILL_VMEM_BYTES`` so a
    tile that fits there compiles as before."""
    scratch = rows * (Dh + 2 * 128) * 4              # acc, m and l tiles
    q_in = 2 * rows * Dh * q_itemsize                # double-buffered blocks
    acc_out = 2 * rows * Dh * 4
    ml_out = 2 * 2 * rows * 4                        # m and l rows
    need = 2 * (scratch + q_in + acc_out + ml_out)
    return max(_PREFILL_VMEM_BYTES, need)


def _prefill_live_blocks(qs_slot, pg: int, C: int, ct: int, nt: int, bp: int):
    """(B, nt) int32 trip counts of the prefill walk: per row tile, the
    blocks up to its last live one.  Slot ``j`` is live for tile ``t`` when
    its first position ``j * pg`` lies at or before the tile's last real
    row, ``q_start + min((t + 1) * ct, C) - 1`` (``qs_slot``; ``NO_SLOT``
    on slots another device owns)."""
    M = qs_slot.shape[1]
    first = jnp.arange(M, dtype=jnp.int32) * pg
    horizon = jnp.minimum(jnp.arange(1, nt + 1, dtype=jnp.int32) * ct, C) - 1
    live = first[None, None, :] <= (
        qs_slot[:, None, :] + horizon[None, :, None]
    )
    return _live_blocks(live, bp)


def _prefill_partials(
    q, k_pages, v_pages, block_tables, qs_slot, layer,
    *, consts, policy_k, constant_k, policy_v, constant_v, interpret,
):
    """Unnormalized chunked-q prefill partials over the live block-table
    walk.

    ``qs_slot`` is (B, M) int32 — the chunk-row-0 context position carried
    *per block slot*.  On a single device every slot of request ``b`` holds
    the same value; under the sharded walk non-owned slots hold ``NO_SLOT``
    and are never read.  The grid is ``(B, row tiles)``: a cell holds
    ``ct`` chunk rows of every query head (``_prefill_row_tile``; the chunk
    is padded to whole tiles, the padded rows dropped) and copies
    the pages of the slots up to its last row from HBM, in blocks of ``bp``
    (``_block_pages``), double-buffered.  Every tile repairs the pages it
    reads; the last tile, which reads every live page, counts them.  Slots
    past the chunk's last row are neither fetched nor detected and read 0
    in ``slot_counts``.  Returns ``(acc (B, C, H, Dh) f32, m (B, C*H) f32,
    l (B, C*H) f32, slot_counts, counts)``.
    """
    B, C, H, Dh = q.shape
    P, L, pg, Kh, _ = k_pages.shape
    _check_walk_pool(k_pages, v_pages)
    assert H % Kh == 0, (H, Kh)
    group = H // Kh
    M = block_tables.shape[1]
    bp = _block_pages(M, pg)
    ct = _prefill_row_tile(C, H, group)
    nt = -(-C // ct)
    Cp = nt * ct                         # chunk rows padded to whole tiles
    R = ct * group
    qs_slot = jnp.asarray(qs_slot, jnp.int32)
    n_blocks = _prefill_live_blocks(qs_slot, pg, C, ct, nt, bp)
    # rows per KV head, ordered (chunk row, head of the group), in the
    # contraction dtype: the relayout and cast happen once, outside the walk
    cdt = k_pages.dtype if q.dtype == k_pages.dtype else jnp.float32
    q = jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, 0)))
    qh = jnp.swapaxes(q.reshape(B, Cp, Kh, group, Dh), 1, 2)
    qh = qh.reshape(B, Kh, Cp * group, Dh).astype(cdt)

    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # detector consts, block tables, q_start, live blocks, layer
        num_scalar_prefetch=5,
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, Kh, R, Dh), lambda b, t, *_: (b, 0, t, 0)),
            # the pool leaves stay in HBM: the kernel copies live pages
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, Kh, R, Dh), lambda b, t, *_: (b, 0, t, 0)),
            # (B, Kh, 1, C*G): a unit second-minor axis keeps the block's
            # last two dims tiled as the TPU requires
            pl.BlockSpec((1, Kh, 1, R), lambda b, t, *_: (b, 0, 0, t)),
            pl.BlockSpec((1, Kh, 1, R), lambda b, t, *_: (b, 0, 0, t)),
            common.smem_spec(),     # slot counts (B, M)
            common.smem_spec(),     # counts int32[8]
        ],
        scratch_shapes=_walk_scratch(bp, k_pages, v_pages) + [
            pltpu.VMEM((Kh, R, Dh), jnp.float32),
            pltpu.VMEM((Kh, R, 128), jnp.float32),
            pltpu.VMEM((Kh, R, 128), jnp.float32),
        ],
    )
    acc, m, l, slot_counts, counts = pl.pallas_call(
        functools.partial(
            _paged_prefill_kernel,
            sm_scale=1.0 / math.sqrt(Dh),
            fill_k=_fill_bits(policy_k, constant_k, k_pages.dtype),
            fill_v=_fill_bits(policy_v, constant_v, v_pages.dtype),
            pg=pg,
            n_kv=Kh,
            group=group,
            nc=C,
            ct=ct,
            bp=bp,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Kh, Cp * group, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Kh, 1, Cp * group), jnp.float32),
            jax.ShapeDtypeStruct((B, Kh, 1, Cp * group), jnp.float32),
            jax.ShapeDtypeStruct((B, M), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_prefill_vmem_bytes(
                ct * H, Dh, jnp.dtype(cdt).itemsize
            )
        ),
        interpret=interpret,
    )(
        consts,
        jnp.asarray(block_tables, jnp.int32),
        qs_slot,
        n_blocks,
        jnp.asarray(layer, jnp.int32).reshape(1),
        qh, k_pages, v_pages,
    )

    # back to rows ordered (chunk row, query head), padding dropped
    def rows_first(x):
        x = x.reshape(B, Kh, Cp, group, *x.shape[3:])[:, :, :C]
        return jnp.swapaxes(x, 1, 2).reshape(B, C * H, *x.shape[4:])

    acc = rows_first(acc).reshape(B, C, H, Dh)
    return acc, rows_first(m[:, :, 0]), rows_first(l[:, :, 0]), slot_counts, counts


def _prefill_normalize(out_dtype, acc, l):
    """The serial prefill epilogue: divide the f32 accumulator by the row
    sums and cast — the same ops, in the same row order, the kernel used to
    run in its flush, so moving it out of the kernel is bit-transparent."""
    B, C, H, Dh = acc.shape
    denom = jnp.maximum(l, 1e-30)                            # (B, C*H)
    out = acc.reshape(B, C * H, Dh) / denom[..., None]
    return out.astype(out_dtype).reshape(B, C, H, Dh)


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "constant", "include_inf", "interpret",
        "detector_k", "detector_v",
        "policy_k", "constant_k", "policy_v", "constant_v",
    ),
)
def paged_prefill_raw(
    q: jax.Array,              # (B, C, H, Dh) one causal chunk per request
    k_pages: jax.Array,        # (P, L, pg, Kh, Dh)
    v_pages: jax.Array,        # (P, L, pg, Kh, Dh)
    block_tables: jax.Array,   # (B, M) int32
    q_start: jax.Array,        # (B,) int32 — context position of chunk row 0
    layer: jax.Array,          # int32 scalar — L row of the pool leaves
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR,
    detector_v=DEFAULT_DETECTOR,
    policy_k: Optional[str] = None,
    constant_k: Optional[float] = None,
    policy_v: Optional[str] = None,
    constant_v: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of chunked-q paged prefill with fused on-read repair.

    The q chunk (already written into the pool by the caller) attends over
    the live pages of the request's block table — the split-K walk's block
    copies, per-operand detector constants and value-independent fills,
    with the chunk's causal mask (`key position <= q_start + row`) instead
    of a single decode position.  Chunk row ``c`` must sit at context
    position ``q_start[b] + c``; rows past the real chunk length produce
    garbage the caller discards.  Slots whose first position lies past the
    chunk's last row (``q_start + C - 1``), null padding among them, are
    neither fetched nor detected: ``slot_counts`` is 0 there.  Every read
    page is repaired and counted once.  Returns ``(out (B, C, H, Dh),
    slot_counts (B, M) int32, counts int32[8])``.
    """
    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B = q.shape[0]
    M = block_tables.shape[1]
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)
    qs_slot = jnp.broadcast_to(
        jnp.asarray(q_start, jnp.int32)[:, None], (B, M)
    )
    acc, m, l, slot_counts, counts = _prefill_partials(
        q, k_pages, v_pages, block_tables, qs_slot, layer,
        consts=consts,
        policy_k=policy_k, constant_k=constant_k,
        policy_v=policy_v, constant_v=constant_v,
        interpret=interpret,
    )
    return _prefill_normalize(q.dtype, acc, l), slot_counts, counts


def paged_prefill(
    q: jax.Array,              # (B, C, H, Dh)
    k_pages: jax.Array,        # (P, pg, Kh, Dh) or (P, L, pg, Kh, Dh)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, M) int32
    q_start: jax.Array,        # (B,) int32
    *,
    layer: int = 0,
    **kw,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Convenience entry mirroring ``paged_attention``: layer-free pools,
    ``page_counts`` scatter-added to the pool's page axis."""
    if k_pages.ndim == 4:
        k_pages = k_pages[:, None]
        v_pages = v_pages[:, None]
    out, slot_counts, counts = paged_prefill_raw(
        q, k_pages, v_pages, block_tables, q_start,
        jnp.asarray(layer, jnp.int32), **kw,
    )
    page_counts = jnp.zeros((k_pages.shape[0],), jnp.int32).at[
        jnp.asarray(block_tables, jnp.int32)
    ].add(slot_counts)
    return out, page_counts, counts


# --------------------------------------------------------------------------
# Split-K flash decoding: the live page walk, in blocks of pages.
# --------------------------------------------------------------------------
def _splitk_live_blocks(pos_slot, pg: int, splits: int, bp: int):
    """(B, splits) int32 trip counts of the split-K walk: per split, the
    number of ``bp``-page blocks up to and including its last live one.  A
    slot ``j`` is live when its first position ``j * pg`` lies within its
    bound (``pos_slot``; -1 on slots another device owns)."""
    B, M = pos_slot.shape
    first = jnp.arange(M, dtype=jnp.int32) * pg
    live = (first[None, :] <= pos_slot).reshape(B, splits, M // splits)
    return _live_blocks(live, bp)


def _paged_splitk_kernel(
    consts_ref,      # int32[2, 8]      detector constants: row 0 K, row 1 V
    bt_ref,          # int32[B, M]      block tables (page ids of the copies)
    pos_ref,         # int32[B, M]      last valid position, per block slot
    nblk_ref,        # int32[B, S]      live blocks to walk, per split
    layer_ref,       # int32[1]         which L row of the pool leaves
    q_ref, k_hbm, v_hbm,
    o_ref, mo_ref, lo_ref, slot_ref, counts_ref,
    k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
    *, sm_scale: float, fill_k: int, fill_v: int,
    pg: int, n_kv: int, group: int, ns: int, bp: int,
):
    b, g = pl.program_id(0), pl.program_id(1)
    base = g * ns                        # first block-table slot of the split
    H = n_kv * group
    N = bp * pg
    Dh = q_ref.shape[-1]

    @pl.when((b == 0) & (g == 0))
    def _init_counts():
        common.zero_counts(counts_ref, 8)

    _zero_slots(slot_ref, b, base, ns)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def live(j):
        # per-SLOT bound: on a single device every slot of request b
        # carries pos[b]; under the sharded walk non-owned slots carry -1
        return j * pg <= pos_ref[b, j]

    def attend(i, buf):
        j0 = base + i * bp
        k_heads = _block_heads(
            k_buf, buf, common.consts_row(consts_ref, 0), fill_k, N
        )
        v_heads = _block_heads(
            v_buf, buf, common.consts_row(consts_ref, 1), fill_v, N
        )
        _count_block(
            slot_ref, counts_ref, k_heads, v_heads,
            b=b, j0=j0, pg=pg, live=live,
        )

        # ---- online softmax over the block's N = bp * pg keys ----
        # bf16 products are exact in f32: a bf16 pool and query contract
        # in bf16 with f32 accumulation, anything else in f32
        cdt = k_buf.dtype if q_ref.dtype == k_buf.dtype else jnp.float32
        q = q_ref[0].reshape(n_kv, group, Dh).astype(cdt)
        s = jnp.stack([
            jax.lax.dot_general(
                q[h], k_heads[h][0].astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(n_kv)
        ]) * sm_scale                                        # (Kh, G, N)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N), 2)
        bound = jnp.full((1, 1, N), pos_ref[b, j0], jnp.int32)
        for p in range(1, bp):
            bound = jnp.where(lane >= p * pg, pos_ref[b, j0 + p], bound)
        s = jnp.where(j0 * pg + lane <= bound, s, NEG_INF)
        s2 = s.reshape(H, N)

        m_prev = m_ref[:, 0]                                 # (H,)
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1))
        # empty-walk guard: a split whose live keys are all masked (the
        # sharded walk's non-owned slots) keeps (m, l, acc) = (-inf, 0, 0)
        # exactly — a bare exp(s - m) would be exp(0) = 1 per masked lane
        p_ = jnp.where(
            s2 > NEG_INF * 0.5, jnp.exp(s2 - m_new[:, None]), 0.0
        )                                                    # (H, N)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p_, axis=-1)
        # softmax weights quantize to the cache dtype before the value
        # contraction, as in the serial walk and the gathered path
        p3 = p_.reshape(n_kv, group, N).astype(v_buf.dtype)
        pv = jnp.stack([
            jax.lax.dot_general(
                p3[h], v_heads[h][0].astype(v_buf.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(n_kv)
        ])                                                   # (Kh, G, Dh)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv.reshape(H, Dh)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    _walk_live_blocks(
        nblk_ref[b, g], live, attend,
        bt_ref=bt_ref, k_hbm=k_hbm, v_hbm=v_hbm, k_buf=k_buf, v_buf=v_buf,
        sems=sems, b=b, base=base, lay=layer_ref[0], bp=bp,
    )

    # raw partials — normalization happens in the LSE merge stage
    o_ref[0, 0] = acc_ref[...]
    mo_ref[0, 0, 0] = m_ref[:, 0]
    lo_ref[0, 0, 0] = l_ref[:, 0]
def _splitk_partials(
    q, k_pages, v_pages, block_tables, pos_slot, layer,
    *, splits, consts, policy_k, constant_k, policy_v, constant_v, interpret,
):
    """Unnormalized split-K decode partials over the live block-table walk.

    ``pos_slot`` is (B, M) int32 — the inclusive position bound carried
    *per block slot*.  On a single device every slot of request ``b`` holds
    ``positions[b]``; under the sharded walk non-owned slots hold ``-1``.
    A slot is read only when its first position lies within its bound:
    each grid cell ``(b, split)`` copies its live slots' pages from HBM in
    blocks of ``bp`` (``_block_pages``), double-buffered, and walks
    no further than its last live block.  Unread slots are neither fetched
    nor detected and read 0 in ``slot_counts``.  Returns ``(o_part (B,
    splits, H, Dh) f32, m_part (B, splits, H) f32, l_part (B, splits, H)
    f32, slot_counts, counts)``.
    """
    B, H, Dh = q.shape
    P, L, pg, Kh, _ = k_pages.shape
    _check_walk_pool(k_pages, v_pages)
    assert H % Kh == 0, (H, Kh)
    group = H // Kh
    M = block_tables.shape[1]
    assert splits >= 1 and M % splits == 0, (
        f"splits={splits} must divide the block-table width M={M}"
    )
    ns = M // splits
    bp = _block_pages(ns, pg)
    sm_scale = 1.0 / math.sqrt(Dh)
    pos_slot = jnp.asarray(pos_slot, jnp.int32)
    n_blocks = _splitk_live_blocks(pos_slot, pg, splits, bp)

    from jax.experimental.pallas import tpu as pltpu  # local: CPU-safe import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # detector consts, block tables, positions, live blocks, layer
        num_scalar_prefetch=5,
        grid=(B, splits),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, g, *_: (b, 0, 0)),
            # the pool leaves stay in HBM: the kernel copies live pages
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, Dh), lambda b, g, *_: (b, g, 0, 0)),
            # (B, splits, 1, H): a unit second-minor axis keeps the
            # block's last two dims equal to the array's (TPU tiling)
            pl.BlockSpec((1, 1, 1, H), lambda b, g, *_: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, 1, H), lambda b, g, *_: (b, g, 0, 0)),
            common.smem_spec(),     # slot counts (B, M)
            common.smem_spec(),     # counts int32[8]
        ],
        scratch_shapes=_walk_scratch(bp, k_pages, v_pages) + [
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    o, m, l, slot_counts, counts = pl.pallas_call(
        functools.partial(
            _paged_splitk_kernel,
            sm_scale=sm_scale,
            fill_k=_fill_bits(policy_k, constant_k, k_pages.dtype),
            fill_v=_fill_bits(policy_v, constant_v, v_pages.dtype),
            pg=pg,
            n_kv=Kh,
            group=group,
            ns=ns,
            bp=bp,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, splits, H, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, M), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        ],
        interpret=interpret,
    )(
        consts,
        jnp.asarray(block_tables, jnp.int32),
        pos_slot,
        n_blocks,
        jnp.asarray(layer, jnp.int32).reshape(1),
        q, k_pages, v_pages,
    )
    return o, m[:, :, 0], l[:, :, 0], slot_counts, counts


@functools.partial(
    jax.jit,
    static_argnames=(
        "splits", "policy", "constant", "include_inf", "interpret",
        "detector_k", "detector_v",
        "policy_k", "constant_k", "policy_v", "constant_v",
    ),
)
def paged_attention_splitk_raw(
    q: jax.Array,              # (B, H, Dh)
    k_pages: jax.Array,        # (P, L, pg, Kh, Dh)
    v_pages: jax.Array,        # (P, L, pg, Kh, Dh)
    block_tables: jax.Array,   # (B, M) int32
    positions: jax.Array,      # (B,) int32, inclusive
    layer: jax.Array,          # int32 scalar — L row of the pool leaves
    *,
    splits: int,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR,
    detector_v=DEFAULT_DETECTOR,
    policy_k: Optional[str] = None,
    constant_k: Optional[float] = None,
    policy_v: Optional[str] = None,
    constant_v: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-K paged decode: flash-decoding over the live block-table walk.

    The M block-table slots are partitioned into ``splits`` contiguous
    groups, each walked by its own grid cell into an unnormalized partial
    ``(acc, m, l)``; a log-sum-exp merge reduce stage combines the partials
    (colossal-ai ``flash_decoding.py``'s mid_o/mid_o_lse staging).  A cell
    copies only the pages of its live slots (first position ``<= pos``),
    in blocks of several pages, so its work follows the request's context
    and not the table's width.  Null padding and pages past the position
    are neither fetched nor detected: ``slot_counts`` is 0 there.  Splits
    with no live slot carry ``m = -inf`` and zero weight into the merge.
    Every read page is repaired in VMEM and counted exactly as the serial
    kernel does, so the two walks' ``slot_counts`` agree.  Returns
    ``(out (B, H, Dh), slot_counts (B, M) int32, counts int32[8])``.
    """
    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B = q.shape[0]
    M = block_tables.shape[1]
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)
    pos_slot = jnp.broadcast_to(
        jnp.asarray(positions, jnp.int32)[:, None], (B, M)
    )
    o_part, m_part, l_part, slot_counts, counts = _splitk_partials(
        q, k_pages, v_pages, block_tables, pos_slot, layer,
        splits=splits, consts=consts,
        policy_k=policy_k, constant_k=constant_k,
        policy_v=policy_v, constant_v=constant_v,
        interpret=interpret,
    )
    out = _lse_merge(q.dtype, o_part, m_part, l_part)
    return out, slot_counts, counts


def paged_attention_splitk(
    q: jax.Array,              # (B, H, Dh)
    k_pages: jax.Array,        # (P, pg, Kh, Dh) or (P, L, pg, Kh, Dh)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, M) int32
    positions: jax.Array,      # (B,) int32, inclusive
    *,
    splits: int,
    layer: int = 0,
    **kw,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Convenience entry mirroring ``paged_attention`` for the split-K
    variant: layer-free pools, page-axis ``page_counts``."""
    if k_pages.ndim == 4:
        k_pages = k_pages[:, None]
        v_pages = v_pages[:, None]
    out, slot_counts, counts = paged_attention_splitk_raw(
        q, k_pages, v_pages, block_tables, positions,
        jnp.asarray(layer, jnp.int32), splits=splits, **kw,
    )
    page_counts = jnp.zeros((k_pages.shape[0],), jnp.int32).at[
        jnp.asarray(block_tables, jnp.int32)
    ].add(slot_counts)
    return out, page_counts, counts


# --------------------------------------------------------------------------
# Device-local sharded walk: page ownership follows the pool's "page"→axis
# sharding rule, so decode/prefill/split-K reads never cross device
# boundaries (the scrub_sharded pattern, applied to the serving hot path).
# --------------------------------------------------------------------------
#
#   global block table (B, M)       device d owns pool rows [lo, lo + P/nd)
#   ┌──────────────────────┐
#   │ 5  2  9  null  ...   │ ──►  d0: slots with page ∈ [0, P/nd)   others
#   └──────────────────────┘       d1: slots with page ∈ [P/nd, …)  masked
#                                   ⋮   (bound/-qstart sentinel, gate off)
#   each device walks its OWN shard rows only → partials (acc, m, l)
#   all_gather → per-split merge over devices → LSE merge over splits;
#   psum(slot_counts, counts)
#
# Every block-table slot is owned by exactly one device (the null page by
# the device holding the pool's last row), so the psum'd integer counts are
# bit-identical to the serial kernel's, and the merged output is
# bit-identical to `paged_*_shard_ref` — the same partition computed shard
# by shard on one device — and to the single-device kernel wherever no
# split straddles a shard boundary.


def _owned_remap(block_tables, lo, p_local):
    """Ownership mask + shard-local row remap for one device's page range.
    Non-owned slots are remapped to local row 0 with a sentinel bound, so
    neither live walk fetches them and every device keeps one grid shape."""
    owned = (block_tables >= lo) & (block_tables < lo + p_local)
    return owned, jnp.where(owned, block_tables - lo, 0)


def _shard_merge(out_dtype, o, m, l):
    """Merge per-device partials laid out ``(B, nd, S, ...)``: each split's
    device partials combine first, then the splits LSE-merge exactly as
    on one device.  A split whose pages one device owns passes the first
    stage unchanged (weight exp(0) = 1; the other devices' partials are
    empty and weigh 0), so a walk whose splits never straddle a shard
    boundary is bit-identical to the single-device kernel."""
    m_s = jnp.max(m, axis=1)                                 # (B, S, H)
    live = m > NEG_INF * 0.5
    w = jnp.where(live, jnp.exp(m - m_s[:, None]), 0.0)      # (B, nd, S, H)
    l_s = jnp.sum(w * l, axis=1)
    o_s = jnp.sum(w[..., None] * o, axis=1)
    return _lse_merge(out_dtype, o_s, m_s, l_s)


def _device_major_merge(out_dtype, o, m, l, axis):
    """all_gather each device's partials — device d's partial s lands at
    ``(b, d, s)``, the layout `paged_*_shard_ref` stacks — and merge them
    with ``_shard_merge``, so parity with the oracle is bitwise."""
    def gather(x):
        return jnp.moveaxis(jax.lax.all_gather(x, axis), 0, 1)

    return _shard_merge(out_dtype, gather(o), gather(m), gather(l))


def paged_attention_sharded(
    q: jax.Array,              # (B, H, Dh)
    k_pages: jax.Array,        # (P, L, pg, Kh, Dh), page axis sharded
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, M) int32 — GLOBAL page ids
    positions: jax.Array,      # (B,) int32, inclusive
    layer: jax.Array,          # int32 scalar
    *,
    mesh,
    axis: str,
    splits: int = 1,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR,
    detector_v=DEFAULT_DETECTOR,
    policy_k: Optional[str] = None,
    constant_k: Optional[float] = None,
    policy_v: Optional[str] = None,
    constant_v: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device-local paged decode over a page-axis-sharded pool.

    Each device walks the full (B, M) block table but attends only to the
    slots whose page lives in its shard (non-owned slots: position bound
    ``-1`` → never fetched, counted 0).  ``splits > 1`` composes split-K
    *within* each device's walk, yielding ``nd × splits`` partials.  Counts are psum'd (each slot
    counted exactly once, bit-identical to the serial kernel); the output
    merges each split's device partials, then the splits (``_shard_merge``;
    bit-identical to ``paged_attention_shard_ref``, and to the split-K
    kernel when no split straddles a shard boundary).  Returns the same
    triple as ``paged_attention_raw``.
    """
    from jax.sharding import PartitionSpec

    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    P_pages = k_pages.shape[0]
    nd = mesh.shape[axis]
    assert P_pages % nd == 0, (
        f"page axis {P_pages} must divide the '{axis}' mesh axis ({nd})"
    )
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32)

    def local(qd, kl, vl, btd, posd, layd, cd):
        p_local = kl.shape[0]
        lo = jax.lax.axis_index(axis) * p_local
        owned, bt_local = _owned_remap(btd, lo, p_local)
        pos_slot = jnp.where(owned, posd[:, None], -1)
        o, m, l, slot, counts = _splitk_partials(
            qd, kl, vl, bt_local, pos_slot, layd,
            splits=splits, consts=cd,
            policy_k=policy_k, constant_k=constant_k,
            policy_v=policy_v, constant_v=constant_v,
            interpret=interpret,
        )
        out = _device_major_merge(qd.dtype, o, m, l, axis)
        return out, jax.lax.psum(slot, axis), jax.lax.psum(counts, axis)

    spec = PartitionSpec(axis)
    rep = PartitionSpec()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep, spec, spec, rep, rep, rep, rep),
        out_specs=(rep, rep, rep),
        check_vma=False,
    )(q, k_pages, v_pages, bt, pos, lay, consts)


def paged_attention_shard_ref(
    q, k_pages, v_pages, block_tables, positions, layer,
    *, n_shards: int, splits: int = 1,
    policy: str = "zero", constant: float = 0.0, include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR, detector_v=DEFAULT_DETECTOR,
    policy_k=None, constant_k=None, policy_v=None, constant_v=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-device oracle of ``paged_attention_sharded``: the identical
    ownership partition and merge, computed shard by shard on one device.
    The sharded entry must match this bit for bit — it is the parity
    target of the multidev lane (the *serial* kernel differs in
    accumulation grouping, so its float output is only allclose)."""
    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    P_pages = k_pages.shape[0]
    assert P_pages % n_shards == 0, (P_pages, n_shards)
    p_local = P_pages // n_shards
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32)
    os_, ms_, ls_ = [], [], []
    slot_tot = None
    counts_tot = None
    for d in range(n_shards):
        lo = d * p_local
        owned, bt_local = _owned_remap(bt, lo, p_local)
        pos_slot = jnp.where(owned, pos[:, None], -1)
        o, m, l, slot, counts = _splitk_partials(
            q, k_pages[lo:lo + p_local], v_pages[lo:lo + p_local],
            bt_local, pos_slot, lay,
            splits=splits, consts=consts,
            policy_k=policy_k, constant_k=constant_k,
            policy_v=policy_v, constant_v=constant_v,
            interpret=interpret,
        )
        os_.append(o)
        ms_.append(m)
        ls_.append(l)
        slot_tot = slot if slot_tot is None else slot_tot + slot
        counts_tot = counts if counts_tot is None else counts_tot + counts
    out = _shard_merge(
        q.dtype,
        jnp.stack(os_, axis=1),
        jnp.stack(ms_, axis=1),
        jnp.stack(ls_, axis=1),
    )
    return out, slot_tot, counts_tot


def paged_prefill_sharded(
    q: jax.Array,              # (B, C, H, Dh)
    k_pages: jax.Array,        # (P, L, pg, Kh, Dh), page axis sharded
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, M) int32 — GLOBAL page ids
    q_start: jax.Array,        # (B,) int32
    layer: jax.Array,          # int32 scalar
    *,
    mesh,
    axis: str,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR,
    detector_v=DEFAULT_DETECTOR,
    policy_k: Optional[str] = None,
    constant_k: Optional[float] = None,
    policy_v: Optional[str] = None,
    constant_v: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device-local chunked-q paged prefill over a page-axis-sharded pool.

    The sharded analogue of ``paged_prefill_raw``: non-owned block slots
    carry the ``NO_SLOT`` q_start sentinel (never read, counted 0), each
    device emits one unnormalized chunk partial, and
    ``_shard_merge`` normalizes — bit-identical to
    ``paged_prefill_shard_ref``, and to ``paged_prefill_raw`` when one
    device owns every attended page.
    """
    from jax.sharding import PartitionSpec

    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B, C, H, Dh = q.shape
    P_pages = k_pages.shape[0]
    nd = mesh.shape[axis]
    assert P_pages % nd == 0, (
        f"page axis {P_pages} must divide the '{axis}' mesh axis ({nd})"
    )
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)
    bt = jnp.asarray(block_tables, jnp.int32)
    qs = jnp.asarray(q_start, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32)

    def local(qd, kl, vl, btd, qsd, layd, cd):
        p_local = kl.shape[0]
        lo = jax.lax.axis_index(axis) * p_local
        owned, bt_local = _owned_remap(btd, lo, p_local)
        qs_slot = jnp.where(owned, qsd[:, None], NO_SLOT)
        acc, m, l, slot, counts = _prefill_partials(
            qd, kl, vl, bt_local, qs_slot, layd,
            consts=cd,
            policy_k=policy_k, constant_k=constant_k,
            policy_v=policy_v, constant_v=constant_v,
            interpret=interpret,
        )
        # one partial per device: rows are the (C, H) chunk rows
        merged = _device_major_merge(
            qd.dtype,
            acc.reshape(B, 1, C * H, Dh), m[:, None], l[:, None], axis,
        )
        out = merged.reshape(B, C, H, Dh)
        return out, jax.lax.psum(slot, axis), jax.lax.psum(counts, axis)

    spec = PartitionSpec(axis)
    rep = PartitionSpec()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep, spec, spec, rep, rep, rep, rep),
        out_specs=(rep, rep, rep),
        check_vma=False,
    )(q, k_pages, v_pages, bt, qs, lay, consts)


def paged_prefill_shard_ref(
    q, k_pages, v_pages, block_tables, q_start, layer,
    *, n_shards: int,
    policy: str = "zero", constant: float = 0.0, include_inf: bool = True,
    interpret: Optional[bool] = None,
    detector_k=DEFAULT_DETECTOR, detector_v=DEFAULT_DETECTOR,
    policy_k=None, constant_k=None, policy_v=None, constant_v=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-device oracle of ``paged_prefill_sharded`` (see
    ``paged_attention_shard_ref``)."""
    if interpret is None:
        interpret = common.default_interpret()
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B, C, H, Dh = q.shape
    P_pages = k_pages.shape[0]
    assert P_pages % n_shards == 0, (P_pages, n_shards)
    p_local = P_pages // n_shards
    consts = _detector_consts(detector_k, detector_v, k_pages.dtype, include_inf)
    bt = jnp.asarray(block_tables, jnp.int32)
    qs = jnp.asarray(q_start, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32)
    os_, ms_, ls_ = [], [], []
    slot_tot = None
    counts_tot = None
    for d in range(n_shards):
        lo = d * p_local
        owned, bt_local = _owned_remap(bt, lo, p_local)
        qs_slot = jnp.where(owned, qs[:, None], NO_SLOT)
        acc, m, l, slot, counts = _prefill_partials(
            q, k_pages[lo:lo + p_local], v_pages[lo:lo + p_local],
            bt_local, qs_slot, lay,
            consts=consts,
            policy_k=policy_k, constant_k=constant_k,
            policy_v=policy_v, constant_v=constant_v,
            interpret=interpret,
        )
        os_.append(acc.reshape(B, 1, C * H, Dh))
        ms_.append(m[:, None])
        ls_.append(l[:, None])
        slot_tot = slot if slot_tot is None else slot_tot + slot
        counts_tot = counts if counts_tot is None else counts_tot + counts
    merged = _shard_merge(
        q.dtype,
        jnp.stack(os_, axis=1),
        jnp.stack(ms_, axis=1),
        jnp.stack(ls_, axis=1),
    )
    return merged.reshape(B, C, H, Dh), slot_tot, counts_tot
