"""Pure-jnp oracles for every Pallas kernel (bit-exact counter semantics).

Each oracle replays the kernel's *tiling* where it matters (neighbor_mean is
a per-tile statistic; event counters are per-tile-visit), so tests can assert
exact equality on counters and allclose on values across shape/dtype sweeps.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import detect


def _masks(x):
    bits = detect.bits_of(x)
    return detect.is_nan_bits(bits, x.dtype), detect.is_inf_bits(bits, x.dtype)


def repair_array_ref(
    x: jax.Array,
    *,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    block: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Repair ``x`` exactly as the kernels do, tile-by-tile.

    Returns (fixed, nan_count, inf_count, tiles_with_fatal).  ``block`` is the
    kernel's 2D tile over the trailing-dim-flattened view; None means one tile
    = whole array (policy statistics over everything).
    """
    orig = x.shape
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim >= 2 else x.reshape(1, -1)
    rows, cols = x2.shape
    br, bc = block if block is not None else (rows, cols)
    assert rows % br == 0 and cols % bc == 0, (x2.shape, block)

    nan_m, inf_m = _masks(x2)
    mask = (nan_m | inf_m) if include_inf else nan_m

    # tile view: (nr, nc, br, bc)
    t = x2.reshape(rows // br, br, cols // bc, bc).transpose(0, 2, 1, 3)
    tm = mask.reshape(rows // br, br, cols // bc, bc).transpose(0, 2, 1, 3)

    if policy == "zero":
        rep = jnp.zeros_like(t)
    elif policy == "constant":
        rep = jnp.full_like(t, constant)
    elif policy == "clamp_finite_max":
        rep = jnp.full_like(t, jnp.finfo(x.dtype).max)
    elif policy == "neighbor_mean":
        ok = (~tm).astype(jnp.float32)
        cnt = jnp.maximum(ok.sum(axis=(2, 3), keepdims=True), 1.0)
        tot = jnp.where(~tm, t.astype(jnp.float32), 0.0).sum(
            axis=(2, 3), keepdims=True
        )
        rep = jnp.broadcast_to(tot / cnt, t.shape).astype(x.dtype)
    else:
        raise ValueError(policy)

    fixed = jnp.where(tm, rep, t)
    fixed = fixed.transpose(0, 2, 1, 3).reshape(rows, cols).reshape(orig)
    tiles_fatal = jnp.sum(jnp.any(tm, axis=(2, 3)).astype(jnp.int32))
    return (
        fixed,
        jnp.sum(nan_m.astype(jnp.int32)),
        jnp.sum(inf_m.astype(jnp.int32)) if include_inf else jnp.zeros((), jnp.int32),
        tiles_fatal,
    )


def scrub_ref(
    x, *, policy="zero", constant=0.0, include_inf=True, block=None
):
    """Oracle of kernels.scrub: (fixed, counts[3] = [nan, inf, events])."""
    fixed, n, i, ev = repair_array_ref(
        x, policy=policy, constant=constant, include_inf=include_inf,
        block=block,
    )
    return fixed, jnp.stack([n, i, ev])


def repair_matmul_ref(
    a, b, *, policy="zero", constant=0.0, include_inf=True,
    blocks: Optional[Tuple[int, int, int]] = None, out_dtype=None,
):
    """Oracle of repair_matmul_raw: (c, counts[8]).

    Event counts replay the kernel's visit schedule: each a-tile is visited
    once per j (N/bn times), each b-tile once per i (M/bm times).
    """
    (M, K), (_, N) = a.shape, b.shape
    out_dtype = out_dtype or a.dtype
    if blocks is None:
        bm = bn = bk = None
        a_blk = b_blk = None
        nj = ni = 1
    else:
        bm, bn, bk = blocks
        a_blk, b_blk = (bm, bk), (bk, bn)
        nj, ni = N // bn, M // bm

    fa, nan_a, inf_a, ta = repair_array_ref(
        a, policy=policy, constant=constant, include_inf=include_inf,
        block=a_blk,
    )
    fb, nan_b, inf_b, tb = repair_array_ref(
        b, policy=policy, constant=constant, include_inf=include_inf,
        block=b_blk,
    )
    c = jnp.dot(
        fa.astype(jnp.float32), fb.astype(jnp.float32)
    ).astype(out_dtype)
    counts = jnp.stack([
        nan_a * nj, inf_a * nj, ta * nj,
        nan_b * ni, inf_b * ni, tb * ni,
        jnp.zeros((), jnp.int32),       # ev_total needs the joint schedule
        jnp.zeros((), jnp.int32),
    ])
    return c, counts


def _paged_masks(x, detector, include_inf):
    """Fatal masks of one operand under the paged kernel's detector grammar:
    a ``core.rules.Detector``, the "default" sentinel (legacy NaN(+Inf)),
    or ``None`` — detection disabled."""
    if detector is None:
        z = jnp.zeros(x.shape, jnp.bool_)
        return z, z
    if isinstance(detector, str):          # the "default" sentinel
        from ..core import rules as rules_lib

        detector = rules_lib.Detector(nan=True, inf=include_inf)
    return detector.masks(x)


def _repair_paged_rows(rows, detector, policy, constant, include_inf):
    """Repair (B, M, pg, Kh, Dh) page rows, one (b, m) row per kernel tile,
    with the paged family's per-operand fill grammar.  Returns the repaired
    rows and the per-slot fatal-lane counts (B, M)."""
    nan_m, inf_m = _paged_masks(rows, detector, include_inf)
    mask = nan_m | inf_m
    if policy == "zero":
        rep = jnp.zeros_like(rows)
    elif policy == "constant":
        rep = jnp.full_like(rows, constant)
    elif policy == "clamp_finite_max":
        rep = jnp.full_like(rows, jnp.finfo(rows.dtype).max)
    elif policy == "neighbor_mean":
        ok = (~mask).astype(jnp.float32)
        cnt = jnp.maximum(ok.sum(axis=(2, 3, 4), keepdims=True), 1.0)
        tot = jnp.where(mask, 0.0, rows.astype(jnp.float32)).sum(
            axis=(2, 3, 4), keepdims=True
        )
        rep = jnp.broadcast_to(tot / cnt, rows.shape).astype(rows.dtype)
    else:
        raise ValueError(policy)
    fixed = jnp.where(mask, rep, rows)
    n_fatal = (nan_m | inf_m).astype(jnp.int32).sum(axis=(2, 3, 4))
    return fixed, n_fatal                                      # (B, M)


def _live_slots(M: int, pg: int, pos):
    """(B, M) bool: block-table slots whose first position lies within
    ``pos`` — the slots a decode walk (the context) or a prefill walk (the
    chunk's last row) reads and counts."""
    return jnp.arange(M)[None, :] * pg <= pos[:, None]


def paged_attention_ref(
    q,                 # (B, H, Dh)
    k_pages,           # (P, pg, Kh, Dh) or (P, L, pg, Kh, Dh)
    v_pages,
    block_tables,      # (B, M) int32
    positions,         # (B,) int32, inclusive
    *,
    layer: int = 0,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    detector_k="default",
    detector_v="default",
    policy_k=None,
    constant_k=None,
    policy_v=None,
    constant_v=None,
):
    """Oracle of kernels.paged_attention: gather the block-table pages (the
    very copy the kernel avoids), repair each (page, layer) row as one tile
    — the kernel's repair unit — then full-softmax decode attention over
    the masked positions.  ``policy_k``/``policy_v`` (+ constants) override
    the shared fill per operand, mirroring the kernel's per-tile
    operand-indexed fill selection.  Returns ``(out (B,H,Dh), slot_counts
    (B,M))`` with bit-exact count semantics."""
    if k_pages.ndim == 4:
        k_pages = k_pages[:, None]
        v_pages = v_pages[:, None]
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B, H, Dh = q.shape
    P, L, pg, Kh, _ = k_pages.shape
    G = H // Kh
    bt = jnp.asarray(block_tables, jnp.int32)
    M = bt.shape[1]
    pos = jnp.asarray(positions, jnp.int32)

    k_rows = k_pages[bt, layer]                                # (B, M, pg, Kh, Dh)
    v_rows = v_pages[bt, layer]
    fk, cnt_k = _repair_paged_rows(
        k_rows, detector_k, policy_k, constant_k, include_inf
    )
    fv, cnt_v = _repair_paged_rows(
        v_rows, detector_v, policy_v, constant_v, include_inf
    )
    # slots past the context report nothing (the walk masks their counts)
    slot_counts = jnp.where(_live_slots(M, pg, pos), cnt_k + cnt_v, 0)

    T = M * pg
    fk = fk.reshape(B, T, Kh, Dh)
    fv = fv.reshape(B, T, Kh, Dh)
    qg = q.reshape(B, Kh, G, Dh).astype(jnp.float32)
    s = jnp.einsum(
        "bkgd,btkd->bkgt", qg, fk.astype(jnp.float32)
    ) / math.sqrt(Dh)
    t = jnp.arange(T)
    s = jnp.where(t[None, None, None, :] <= pos[:, None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    # weights quantize to the cache dtype before the value contraction,
    # like the gathered decode and the fused kernel
    out = jnp.einsum(
        "bkgt,btkd->bkgd", w.astype(fv.dtype), fv,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, H, Dh).astype(q.dtype), slot_counts


def paged_prefill_ref(
    q,                 # (B, C, H, Dh) — one causal chunk per request
    k_pages,           # (P, pg, Kh, Dh) or (P, L, pg, Kh, Dh)
    v_pages,
    block_tables,      # (B, M) int32
    q_start,           # (B,) int32 — context position of chunk row 0
    *,
    layer: int = 0,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    detector_k="default",
    detector_v="default",
    policy_k=None,
    constant_k=None,
    policy_v=None,
    constant_v=None,
):
    """Oracle of kernels.paged_prefill: gather, tile-repair, then full
    causal softmax — chunk row ``c`` reads key positions ``<= q_start + c``.
    Rows past the caller's real chunk length are computed like any other
    (the kernel's garbage-row contract); callers compare valid rows only.
    Slots whose first position lies past the chunk's last row are never
    read: they count 0 and contribute nothing, whatever their bits.
    Returns ``(out (B, C, H, Dh), slot_counts (B, M))``."""
    if k_pages.ndim == 4:
        k_pages = k_pages[:, None]
        v_pages = v_pages[:, None]
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B, C, H, Dh = q.shape
    P, L, pg, Kh, _ = k_pages.shape
    G = H // Kh
    bt = jnp.asarray(block_tables, jnp.int32)
    M = bt.shape[1]
    qs = jnp.asarray(q_start, jnp.int32)

    fk, cnt_k = _repair_paged_rows(
        k_pages[bt, layer], detector_k, policy_k, constant_k, include_inf
    )
    fv, cnt_v = _repair_paged_rows(
        v_pages[bt, layer], detector_v, policy_v, constant_v, include_inf
    )
    live = _live_slots(M, pg, qs + C - 1)                      # (B, M)
    slot_counts = jnp.where(live, cnt_k + cnt_v, 0)
    unread = ~live[:, :, None, None, None]
    fk = jnp.where(unread, jnp.zeros((), fk.dtype), fk)
    fv = jnp.where(unread, jnp.zeros((), fv.dtype), fv)

    T = M * pg
    fk = fk.reshape(B, T, Kh, Dh)
    fv = fv.reshape(B, T, Kh, Dh)
    qg = q.reshape(B, C, Kh, G, Dh).astype(jnp.float32)
    s = jnp.einsum(
        "bckgd,btkd->bckgt", qg, fk.astype(jnp.float32)
    ) / math.sqrt(Dh)
    tq = qs[:, None] + jnp.arange(C)[None, :]                  # (B, C)
    t = jnp.arange(T)
    s = jnp.where(
        t[None, None, None, None, :] <= tq[:, :, None, None, None], s, -1e30
    )
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bckgt,btkd->bckgd", w.astype(fv.dtype), fv,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, C, H, Dh).astype(q.dtype), slot_counts


def paged_splitk_ref(
    q,                 # (B, H, Dh)
    k_pages,           # (P, pg, Kh, Dh) or (P, L, pg, Kh, Dh)
    v_pages,
    block_tables,      # (B, M) int32
    positions,         # (B,) int32, inclusive
    *,
    splits: int,
    layer: int = 0,
    policy: str = "zero",
    constant: float = 0.0,
    include_inf: bool = True,
    detector_k="default",
    detector_v="default",
    policy_k=None,
    constant_k=None,
    policy_v=None,
    constant_v=None,
):
    """Oracle of kernels.paged_attention_splitk: per-split softmax partials
    merged by log-sum-exp, with the null-tail guard made explicit — a split
    whose slice holds no valid position carries ``(m, l) = (-inf, 0)`` and
    zero weight into the merge, never its fill values.  Slots past the
    context are never read: they count 0 and contribute nothing, whatever
    their bits.  Returns ``(out (B, H, Dh), slot_counts (B, M))``."""
    if k_pages.ndim == 4:
        k_pages = k_pages[:, None]
        v_pages = v_pages[:, None]
    policy_k = policy if policy_k is None else policy_k
    constant_k = constant if constant_k is None else constant_k
    policy_v = policy if policy_v is None else policy_v
    constant_v = constant if constant_v is None else constant_v
    B, H, Dh = q.shape
    P, L, pg, Kh, _ = k_pages.shape
    G = H // Kh
    bt = jnp.asarray(block_tables, jnp.int32)
    M = bt.shape[1]
    assert splits >= 1 and M % splits == 0, (splits, M)
    ns = M // splits
    pos = jnp.asarray(positions, jnp.int32)

    fk, cnt_k = _repair_paged_rows(
        k_pages[bt, layer], detector_k, policy_k, constant_k, include_inf
    )
    fv, cnt_v = _repair_paged_rows(
        v_pages[bt, layer], detector_v, policy_v, constant_v, include_inf
    )
    live = _live_slots(M, pg, pos)                             # (B, M)
    slot_counts = jnp.where(live, cnt_k + cnt_v, 0)
    unread = ~live[:, :, None, None, None]
    fk = jnp.where(unread, jnp.zeros((), fk.dtype), fk)
    fv = jnp.where(unread, jnp.zeros((), fv.dtype), fv)

    # (B, splits, ns*pg, Kh, Dh): each split sees its contiguous page slice
    fk = fk.reshape(B, splits, ns * pg, Kh, Dh)
    fv = fv.reshape(B, splits, ns * pg, Kh, Dh)
    qg = q.reshape(B, Kh, G, Dh).astype(jnp.float32)
    s = jnp.einsum(
        "bkgd,bstkd->bskgt", qg, fk.astype(jnp.float32)
    ) / math.sqrt(Dh)
    t = (
        jnp.arange(splits)[:, None] * ns * pg + jnp.arange(ns * pg)[None, :]
    )                                                          # (splits, ns*pg)
    valid = t[None, :, None, None, :] <= pos[:, None, None, None, None]
    s = jnp.where(valid, s, -1e30)
    m = jnp.max(s, axis=-1)                                    # (B, s, Kh, G)
    p = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)                                    # (B, s, Kh, G)
    acc = jnp.einsum(
        "bskgt,bstkd->bskgd", p.astype(fv.dtype).astype(jnp.float32), fv.astype(jnp.float32)
    )                                                          # (B, s, Kh, G, Dh)
    m_star = jnp.max(m, axis=1)                                # (B, Kh, G)
    live = m > -5e29
    w = jnp.where(live, jnp.exp(m - m_star[:, None]), 0.0)     # (B, s, Kh, G)
    l_tot = jnp.sum(w * l, axis=1)
    out = jnp.sum(w[..., None] * acc, axis=1) / jnp.maximum(
        l_tot, 1e-30
    )[..., None]
    return out.reshape(B, H, Dh).astype(q.dtype), slot_counts


def flash_attention_ref(
    q, k, v, *, causal=True, policy="zero", constant=0.0, include_inf=True,
    kv_block: Optional[int] = None,
):
    """Oracle of flash_attention_raw: full-softmax attention over the
    tile-repaired K/V.  Returns out only (counter schedule is asserted
    separately in tests via repair_array_ref)."""
    B, H, S, D = q.shape
    _, Kh, T, _ = k.shape
    G = H // Kh
    blk = (kv_block, D) if kv_block else None
    fk, *_ = repair_array_ref(
        k.reshape(-1, D), policy=policy, constant=constant,
        include_inf=include_inf, block=blk,
    )
    fv, *_ = repair_array_ref(
        v.reshape(-1, D), policy=policy, constant=constant,
        include_inf=include_inf, block=blk,
    )
    fk = fk.reshape(k.shape)
    fv = fv.reshape(v.shape)

    kx = jnp.repeat(fk, G, axis=1).astype(jnp.float32)   # (B,H,T,D)
    vx = jnp.repeat(fv, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), kx)
    s = s / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", w, vx)
    return out.astype(q.dtype)
