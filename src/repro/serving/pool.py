"""Paged KV pool: block-table-indexed physical cache pages + the free list.

The pool owns the serving engine's approximate-memory resident.  Physical
layout (``Model.paged_cache_defs``): every leaf is ``(n_pages+1, L,
page_size, K, Dh)`` with the page axis LEADING, so one page is one
contiguous row — the unit of

  * region accounting (the pool tree is pre-registered with the owning
    ``ApproxSpace``, so classification/BER injection/stats are page-exact),
  * fault attribution (per-page repair-event counters, routed back from the
    step that touched the page), and
  * targeted repair (``ApproxSpace.scrub_pages`` / the Pallas page-view
    scrub — scrubbed bytes scale with the *faulted* pages, not the pool).

Row ``n_pages`` is the null page: block tables are padded with it, so
gather/scatter shapes stay static (one compiled executable per run).  It is
included in every repair candidate set — padding lanes are masked out of
attention scores, but a NaN there would still poison the context through
``0 * NaN`` in the value contraction.

Requests never see physical indices: the scheduler hands out block tables
(request-order lists of page ids).  On the decode hot path the engine feeds
the pool leaves + block tables straight into the Pallas paged-attention
kernel (``kernels/paged_attention.py`` — fused on-read repair, no copy);
gather/scatter survive only for prefill and for non-paged-decode fallbacks,
and are call-counted (``n_gathers`` / ``n_scatters``) so tests can assert
the decode path issues zero full-view copies.
"""
from __future__ import annotations

import collections
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from ..core import stats as stats_lib
from ..core.regions import Region
from ..distributed import sharding as sh
from ..nn import module
from ..runtime import ApproxSpace
from .config import ServingConfig


def _is_float(leaf) -> bool:
    return hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)


@jax.jit
def pool_reset_pages(tree: Any, ids: jax.Array) -> Any:
    """Zero the named pages in one fused update (functional on CPU; on TPU
    buffer donation would make this an in-place page clear).  Its program
    is ``jit_pool_reset_pages`` in a profile."""
    return jax.tree.map(
        lambda leaf: leaf.at[ids].set(0) if _is_float(leaf) else leaf, tree
    )


@jax.jit
def _copy_page(tree: Any, src: jax.Array, dst: jax.Array) -> Any:
    """Clone one physical page row into another (the copy-on-write fork of
    the prefix cache — src stays shared, dst becomes the writer's private
    copy).  src/dst are traced scalars: one executable per pool layout."""
    return jax.tree.map(
        lambda leaf: (
            leaf.at[dst].set(leaf[src]) if _is_float(leaf) else leaf
        ),
        tree,
    )


@jax.jit
def _page_view(tree: Any, page: jax.Array) -> Any:
    """One page's rows as a leading-axis-1 tree (same key paths as the pool
    tree, so region/rule classification carries over)."""
    return jax.tree.map(
        lambda leaf: leaf[page][None] if _is_float(leaf) else leaf, tree
    )


@jax.jit
def _write_page(tree: Any, view: Any, page: jax.Array) -> Any:
    """Write a leading-axis-1 page view back into its pool row."""
    return jax.tree.map(
        lambda leaf, v: (
            leaf.at[page].set(v[0].astype(leaf.dtype))
            if _is_float(leaf) else leaf
        ),
        tree, view,
    )


@jax.jit
def _pages_view(tree: Any, ids: jax.Array) -> Any:
    """Several pages' rows as a leading-axis-n tree — the batched
    ``_page_view`` (tier swap-out snapshots whole block tables at once)."""
    return jax.tree.map(
        lambda leaf: leaf[ids] if _is_float(leaf) else leaf, tree
    )


@jax.jit
def _write_pages(tree: Any, views: Any, ids: jax.Array) -> Any:
    """Write leading-axis-n page views back into their pool rows — the
    batched ``_write_page`` (tier swap-in restores whole block tables)."""
    return jax.tree.map(
        lambda leaf, v: (
            leaf.at[ids].set(v.astype(leaf.dtype))
            if _is_float(leaf) else leaf
        ),
        tree, views,
    )


@jax.jit
def _gather(tree: Any, block_tables: jax.Array) -> Any:
    """Pool pages -> contiguous per-request cache views.

    leaf (P, L, pg, K, Dh) x block_tables (R, M) -> (L, R, M*pg, K, Dh) —
    exactly the treedef/axis order of ``Model.cache_defs``, so the gathered
    view feeds ``serve_step`` unchanged.
    """

    def g(leaf):
        v = leaf[block_tables]                    # (R, M, L, pg, ...)
        v = jnp.moveaxis(v, 2, 0)                 # (L, R, M, pg, ...)
        L, R, M, pg = v.shape[:4]
        return v.reshape(L, R, M * pg, *v.shape[4:])

    return jax.tree.map(g, tree)


@jax.jit
def _scatter(tree: Any, view: Any, block_tables: jax.Array) -> Any:
    """Write a per-request cache view back into the pool pages.

    Duplicate block-table entries (null-page padding) collide harmlessly —
    every colliding write targets the null row, whose contents are never
    consumed unmasked.
    """

    def s(leaf, v):
        pg = leaf.shape[2]
        L, R, V = v.shape[:3]
        v = v.reshape(L, R, V // pg, pg, *v.shape[3:])
        v = jnp.moveaxis(v, 0, 2)                 # (R, M, L, pg, ...)
        return leaf.at[block_tables].set(v.astype(leaf.dtype))

    return jax.tree.map(s, tree, view)


class PagedKVPool:
    """Fixed-size KV pages + free list + per-page fault accounting."""

    def __init__(
        self,
        model: Any,
        space: ApproxSpace,
        cfg: ServingConfig,
    ):
        defs = model.paged_cache_defs(cfg.n_pages + 1, cfg.page_size)
        self.tree = module.init_params(defs, jax.random.PRNGKey(cfg.seed))
        self.space = space
        self.cfg = cfg
        self.null_page = cfg.n_pages
        space.regions_for(self.tree)        # pre-register page regions
        # mesh-native pool: register page-axis shardings with the runtime —
        # pages spread over the DP axis (sharding rule "page", degrading to
        # replicated when n_pages+1 does not divide it), so page scrubs
        # repair device-local rows and the space's compiled executables
        # specialize to this placement once.
        self.shardings = None
        if space.mesh is not None:
            rules = space.rules or sh.rules_for_mesh(space.mesh)

            def page_sharding(leaf):
                axes = ("page",) + (None,) * (leaf.ndim - 1)
                spec = sh.spec_for_leaf(axes, leaf.shape, space.mesh, rules)
                return NamedSharding(space.mesh, spec)

            self.shardings = jax.tree.map(page_sharding, self.tree)
            self.tree = jax.device_put(self.tree, self.shardings)

        self._free: collections.deque = collections.deque(range(cfg.n_pages))
        # per-page reference counts: a page leaves the free list with one
        # reference (its allocating request); ``share`` adds holders (other
        # requests, the prefix cache); ``free`` releases one reference and
        # the page returns to the free list only at zero — so preemption can
        # never reclaim a page the cache (or another request) still shares.
        # The null padding page is permanently resident (count pinned to 1).
        self._refcount = np.zeros(cfg.n_pages + 1, np.int64)
        self._refcount[self.null_page] = 1
        # dwell clock (README §Serving engine): ``now`` is the engine's step
        # counter (one step == one injection window); ``page_clean_step``
        # timestamps each page's last scrub/zeroing.  now - clean_step is the
        # dwell the prefix cache charges through ApproxConfig.expected_faults.
        self.now = 0
        self.page_clean_step = np.zeros(cfg.n_pages + 1, np.int64)
        # per-page attribution: repair events routed back from steps that
        # touched the page, and how often each page has been scrubbed
        self.page_events = np.zeros(cfg.n_pages + 1, np.int64)
        self.page_scrubs = np.zeros(cfg.n_pages + 1, np.int64)
        self.scrubbed_bytes = 0
        self.scrub_calls = 0
        # full-view copy ledger: the paged-decode acceptance criterion is
        # that the decode hot path issues ZERO of these (prefill keeps them)
        self.n_gathers = 0
        self.n_scatters = 0

    # -------------------------------------------------------------- geometry
    def page_shard_axis(self) -> Optional[str]:
        """The mesh axis the pool's page axis is genuinely sharded over —
        or None.  Non-None iff EVERY leaf's leading (page) dimension is
        partitioned over the same single mesh axis AND the page count
        divides that axis's size (shard_map needs equal shards; the
        ``spec_for_leaf`` rule degrades to replicated otherwise).  The
        engine uses this to decide whether the fused kernels can run the
        device-local sharded walk (README §Serving engine, "Sharded decode
        & load testing")."""
        if self.shardings is None or self.space.mesh is None:
            return None
        axes = set()
        for s in jax.tree.leaves(self.shardings):
            part = s.spec[0] if len(s.spec) > 0 else None
            if isinstance(part, (tuple, list)):
                if len(part) != 1:
                    return None
                part = part[0]
            axes.add(part)
        if len(axes) != 1:
            return None
        axis = axes.pop()
        if axis is None:
            return None
        if (self.cfg.n_pages + 1) % self.space.mesh.shape[axis] != 0:
            return None
        return axis

    @property
    def total_bytes(self) -> int:
        """Bytes of the whole pool (what a whole-cache scrub processes)."""
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self.tree)
            if _is_float(leaf)
        )

    @property
    def page_bytes(self) -> int:
        return self.total_bytes // (self.cfg.n_pages + 1)

    @property
    def n_free(self) -> int:
        return len(self._free)

    # ------------------------------------------------------------ allocation
    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages (zeroed) or None if the pool cannot satisfy
        the request — admission control / preemption trigger upstream."""
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        if pages:
            # physical pages are recycled memory: reset so a new request
            # never reads a previous tenant's (possibly flipped) lanes
            with TraceAnnotation("pool.reset_pages", pages=n):
                self.tree = pool_reset_pages(
                    self.tree, jnp.asarray(pages, jnp.int32)
                )
            assert all(self._refcount[p] == 0 for p in pages), pages
            self._refcount[pages] = 1
            self.page_clean_step[pages] = self.now    # zeroed == scrubbed
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Add one reference to each page (a new holder: another request
        admitted onto a cached prefix, or the prefix cache itself)."""
        for p in pages:
            if not 0 <= p < self.null_page:
                raise ValueError(f"bad page id {p}")
            if self._refcount[p] <= 0:
                raise RuntimeError(f"sharing free page {p}")
            self._refcount[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Release one reference per page; a page returns to the free list
        only when its last holder lets go.  Releasing a page with no live
        reference is a hard error — before refcounts a double free silently
        duplicated the free-list entry, handing the same physical page to
        two requests."""
        for p in pages:
            if not 0 <= p < self.null_page:
                raise ValueError(f"bad page id {p}")
            if self._refcount[p] <= 0:
                raise RuntimeError(
                    f"double free of page {p} (no live reference)"
                )
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                self._free.append(p)

    def refcount(self, page: int) -> int:
        return int(self._refcount[page])

    def is_free(self, page: int) -> bool:
        return self._refcount[page] == 0

    # ------------------------------------------------------------ dwell clock
    def dwell(self, page: int) -> int:
        """Injection windows (engine steps) since ``page`` was last known
        clean — what the prefix cache charges to an expected-fault estimate
        before re-sharing the page."""
        return int(self.now - self.page_clean_step[page])

    def mark_clean(self, pages: Sequence[int]) -> None:
        self.page_clean_step[sorted(set(pages))] = self.now

    def copy_page(self, src: int, dst: int) -> None:
        """Device-copy page ``src``'s rows into ``dst`` (the prefix cache's
        copy-on-write fork).  The clone inherits the source's dwell stamp —
        its bits are exactly as old as the source's last scrub."""
        self.tree = _copy_page(
            self.tree,
            jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32),
        )
        self.page_clean_step[dst] = self.page_clean_step[src]

    # --------------------------------------------------------- gather/scatter
    def block_table(self, pages: Sequence[int]) -> np.ndarray:
        """Fixed-width block table row, null-padded (static shapes)."""
        M = self.cfg.max_pages_per_request
        assert len(pages) <= M, "request outgrew its block table"
        row = np.full((M,), self.null_page, np.int32)
        row[: len(pages)] = pages
        return row

    def gather(self, block_tables: jax.Array) -> Any:
        self.n_gathers += 1
        return _gather(self.tree, jnp.asarray(block_tables, jnp.int32))

    def scatter(self, view: Any, block_tables: jax.Array) -> None:
        self.n_scatters += 1
        self.tree = _scatter(
            self.tree, view, jnp.asarray(block_tables, jnp.int32)
        )

    # ----------------------------------------------------------------- repair
    def fatal_pages(self, page_ids: Sequence[int]) -> List[int]:
        """DEPRECATED public probe — the paged kernel family emits per-page
        fatal counts as a side effect of the read (prefill AND decode), so
        reactive detection no longer needs a separate scan over resident
        pages.  The probe survives for gathered-view fallbacks (non-paged
        models, ineligible rule sets) via ``PageRepairManager.repair_step``,
        which calls the private ``_probe_fatal_pages`` directly."""
        import warnings

        warnings.warn(
            "PagedKVPool.fatal_pages is deprecated: the paged kernels emit "
            "per-page fatal counts on read (PageRepairManager.repair_counts);"
            " the probe remains only for gathered-view fallback paths",
            DeprecationWarning,
            stacklevel=2,
        )
        return self._probe_fatal_pages(page_ids)

    def _probe_fatal_pages(
        self, page_ids: Sequence[int], read=np.asarray
    ) -> List[int]:
        """The subset of ``page_ids`` holding >=1 fatal lane — the trap
        analogue at page granularity (detection only; no repair).

        "Fatal" is per-leaf: each pool leaf's assigned ``RepairRule``
        supplies the detector (README §RepairRule), so a NaN-only KV rule
        and a range-guarded rule disagree about the same bit pattern by
        design.  The probe gate mirrors the repair gate exactly
        (approximate-region float leaves whose rule fires reactively):
        exact-region/exact-island leaves are never probed, and leaves a
        reactive pass would not repair must not keep re-flagging their
        pages as faulty — that would dispatch a no-op scrub every step
        forever.  ``read`` is the blocking readback of the page flags (the
        engine passes its audited one)."""
        ids = sorted(set(page_ids))
        if not ids:
            return []
        idx = jnp.asarray(ids, jnp.int32)
        regions = self.space.regions_for(self.tree)
        rule_tree, _ = self.space.rules_for(self.tree)
        flags = None
        for leaf, region, rule in zip(
            jax.tree.leaves(self.tree),
            jax.tree.leaves(regions),
            jax.tree.leaves(rule_tree),
        ):
            if not _is_float(leaf) or region is not Region.APPROX:
                continue
            if not rule.fires("reactive"):
                continue
            rows = leaf[idx]
            nan_m, inf_m = rule.detect.masks(rows)
            bad = (nan_m | inf_m).reshape(rows.shape[0], -1).any(axis=1)
            flags = bad if flags is None else flags | bad
        if flags is None:
            return []
        mask = read(flags)
        return [p for p, b in zip(ids, mask) if b]

    def scrub_pages(
        self,
        page_ids: Sequence[int],
        stats: stats_lib.Stats,
        *,
        trigger: str = "reactive",
    ) -> stats_lib.Stats:
        """Targeted scrub of exactly ``page_ids`` (unique'd), with byte
        accounting — the page-granular reactive repair.  The pool tree is
        the resident state, so the compiled executable donates it (in-place
        page repair on device)."""
        ids = sorted(set(page_ids))
        if not ids:
            return stats
        # the plan knows what THIS pass actually repairs (rule gating by
        # trigger): a pass no rule fires on is a no-op — don't dispatch it
        # and don't charge the ledger for work that never happened
        plan = self.space.plan_for(self.tree, scope="pages", trigger=trigger)
        if plan.scope == "none" or plan.page_row_bytes == 0:
            return stats
        self.tree, stats = self.space.scrub_pages(
            self.tree, jnp.asarray(ids, jnp.int32), stats, donate=True,
            trigger=trigger,
        )
        self.page_scrubs[ids] += 1
        self.scrubbed_bytes += len(ids) * plan.page_row_bytes
        self.scrub_calls += 1
        self.mark_clean(ids)
        return stats

    def scrub_all(
        self, stats: stats_lib.Stats, *, trigger: str = "reactive"
    ) -> stats_lib.Stats:
        """Whole-pool scrub (the pre-engine ``scrub_cache`` baseline), with
        byte accounting — gated and charged like ``scrub_pages``: only the
        bytes the pass's firing rules cover."""
        plan = self.space.plan_for(self.tree, scope="tree", trigger=trigger)
        if plan.scope == "none" or plan.bytes_per_run == 0:
            return stats
        self.tree, stats = self.space.scrub(
            self.tree, stats, donate=True, trigger=trigger
        )
        self.page_scrubs += 1
        self.scrubbed_bytes += plan.bytes_per_run
        self.scrub_calls += 1
        self.mark_clean(range(self.cfg.n_pages + 1))
        return stats

    def scrub_scope(
        self,
        scope: str,
        page_ids: Sequence[int],
        stats: stats_lib.Stats,
        *,
        trigger: str = "reactive",
    ) -> stats_lib.Stats:
        """Execute one planned repair pass by ``RepairPlan`` scope — the
        pool's ledger-keeping dispatch for the page repair manager (the
        scope itself comes from ``runtime.plan.serving_scope``; no repair
        decisions are made here).  ``trigger`` tags the pass for rule
        gating (reactive repair vs the background interval sweep)."""
        if scope == "pages":
            return self.scrub_pages(page_ids, stats, trigger=trigger)
        if scope == "tree":
            return self.scrub_all(stats, trigger=trigger)
        assert scope == "none", f"bad plan scope {scope!r}"
        return stats

    def pages_view(self, pages: Sequence[int]) -> Any:
        """Host (numpy) copies of several pages' rows, leading axis in
        ``pages`` order — what the host tier stores on swap-out.  A copy,
        not a view: freeing or recycling the device pages afterwards
        cannot invalidate it."""
        return jax.device_get(
            _pages_view(self.tree, jnp.asarray(list(pages), jnp.int32))
        )

    def write_pages(self, pages: Sequence[int], views: Any) -> None:
        """Write page-row views (leading axis in ``pages`` order) into live
        pool pages — the tier swap-in.  Writing into a free page is a hard
        error: swapped-in contents must land in pages the normal
        allocation path just handed out, never in recycled rows another
        holder could claim."""
        pages = list(pages)
        for p in pages:
            if not 0 <= p < self.null_page:
                raise ValueError(f"bad page id {p}")
            if self._refcount[p] <= 0:
                raise RuntimeError(f"writing into free page {p}")
        self.tree = _write_pages(
            self.tree,
            jax.tree.map(jnp.asarray, views),
            jnp.asarray(pages, jnp.int32),
        )

    def snapshot_page(self, page: int) -> Any:
        """Host (numpy) copy of one page's rows — the prefix cache's
        checkpointed-prefix reference for scrub-on-reuse."""
        return jax.device_get(
            _page_view(self.tree, jnp.asarray(page, jnp.int32))
        )

    def reference_repair_page(
        self, page: int, snapshot: Any, stats: stats_lib.Stats
    ) -> stats_lib.Stats:
        """Repair one page against its host snapshot (``last_checkpoint``
        at page granularity): fatal lanes are restored to the exact bits the
        prefix held when it was cached, not a fill value — the strongest
        repair available, and only a cached prefix has the reference to pay
        for it.  Byte accounting matches ``scrub_pages`` (the reference
        plan's per-run bytes are exactly one page row's rule-gated bytes)."""
        idx = jnp.asarray(page, jnp.int32)
        view = _page_view(self.tree, idx)
        plan = self.space.plan_for(view, scope="reference")
        if plan.bytes_per_run == 0:
            return stats
        ref = jax.tree.map(jnp.asarray, snapshot)
        view, stats = self.space.scrub_with_reference(view, ref, stats)
        self.tree = _write_page(self.tree, view, idx)
        self.page_scrubs[page] += 1
        self.scrubbed_bytes += plan.bytes_per_run
        self.scrub_calls += 1
        self.mark_clean([page])
        return stats

    def attribute(self, page_ids: Sequence[int], n_events: int) -> None:
        """Route ``n_events`` repair events back to the pages a step touched
        (per-page fault ledger for eviction/QoS policies in later PRs)."""
        if n_events and len(page_ids):
            ids = sorted(set(page_ids))
            self.page_events[ids] += n_events
