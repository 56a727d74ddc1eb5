"""Page-granular reactive repair + the demoted background sweep.

The paper's thesis — repair only what faulted — applied at the pool's page
granularity:

  reactive   every engine step knows exactly which pages it touched (the
             scheduled requests' block tables + the null padding page).
             On the paged paths — prefill AND decode — the *fused kernel*
             is the trap: it emits per-page fatal counts as it streams the
             KV lanes, so ``repair_counts`` scrubs exactly the pages that
             faulted with no separate detection pass at all.
             ``repair_step`` keeps probe-based detection (the deprecated
             ``pool.fatal_pages``, now ``_probe_fatal_pages`` internally)
             solely for the gathered-view fallback.  The pre-engine
             baseline — scrub the whole cache whenever anything faulted —
             is kept as ``repair="whole"`` for the bench comparison.

  routed     fused-kernel counter vectors (``kernels.ops`` ``MM_*``/``AT_*``
             layout) reported through ``note_kernel`` are folded into the
             unified stats via ``ApproxSpace.record_kernel`` AND routed back
             to the step's touched pages: they are marked dirty and scrubbed
             on the next repair pass, and the pool's per-page event ledger
             is charged.

  sweep      the old whole-cache ``ScrubSchedule`` interval is demoted to a
             background low-rate sweep: every ``sweep_interval`` steps a
             rotating window of ``sweep_pages`` pages is scrubbed, catching
             flips in cold pages no step touches (their NaNs would otherwise
             sit resident forever — invisible to reactive repair until read).
"""
from __future__ import annotations

from typing import Callable, Iterable, List, Optional, Sequence, Set

import numpy as np
from jax.profiler import TraceAnnotation

from ..core import stats as stats_lib
from ..kernels import ops as kernel_ops
from ..runtime import ApproxSpace, ScrubSchedule, serving_scope
from .config import ServingConfig
from .pool import PagedKVPool


class PageRepairManager:
    """Owns the dirty set, the sweep cursor, and the repair-mode dispatch."""

    def __init__(
        self,
        pool: PagedKVPool,
        space: ApproxSpace,
        cfg: ServingConfig,
        readback: Optional[Callable[[object], np.ndarray]] = None,
    ):
        self.pool = pool
        self.space = space
        self.cfg = cfg
        self.sweep = ScrubSchedule(boundary=False, interval=cfg.sweep_interval)
        self._dirty: Set[int] = set()
        self._sweep_cursor = 0
        self.n_reactive_scrubs = 0
        self.n_sweep_scrubs = 0
        # the engine's audited device->host readback: every blocking device
        # read this manager forces goes through it, so the desynchronized
        # drain's "strictly fewer syncs" claim is auditable
        self._read = readback or np.asarray

    # ----------------------------------------------------------- kernel route
    def note_kernel(self, counts, touched: Iterable[int]) -> None:
        """Fold a Pallas kernel counter vector into the unified stats and
        route its events back to the pages the reporting step touched."""
        self.space.record_kernel(counts)
        events = int(counts[kernel_ops.MM_EV_TOTAL])
        if events > 0:
            # freed pages are skipped: they may already belong to (or be
            # zeroed for) a different request than the one that reported
            pages = [
                p for p in touched
                if p <= self.pool.null_page and not self.pool.is_free(p)
            ]
            self._dirty.update(pages)
            self.pool.attribute(pages, events)

    def mark_dirty(self, pages: Iterable[int]) -> None:
        self._dirty.update(pages)

    # ---------------------------------------------------------------- repair
    def repair_step(
        self, touched: Sequence[int], stats: stats_lib.Stats
    ) -> stats_lib.Stats:
        """One reactive repair pass before the step's compute consumes the
        touched pages.  Detection (the trap analogue) runs over touched ∪
        dirty ∪ {null}; repair granularity is planned by ``RepairPlan``
        (``serving_scope`` maps ``cfg.repair`` to the plan scope — the
        whole-vs-page decision lives in runtime/, not here)."""
        scope = serving_scope(self.cfg.repair)
        if scope == "none":
            return stats
        candidates = set(touched) | self._dirty | {self.pool.null_page}
        with TraceAnnotation("engine.repair", pages=len(candidates)):
            faulty = self.pool._probe_fatal_pages(candidates, read=self._read)
            return self._scrub_faulty(scope, faulty, stats)

    def repair_counts(
        self,
        page_counts,
        covered: Sequence[int],
        stats: stats_lib.Stats,
        defer: Optional[List] = None,
    ) -> stats_lib.Stats:
        """Reactive repair driven by the fused paged kernels' per-page
        fatal counts — the replacement for the ``fatal_pages`` probe on
        every paged path (prefill and decode).  ``page_counts`` is the
        ``(n_pages+1,)`` vector the compiled step emitted (or several
        steps' vectors summed); ``covered`` is the page set
        the kernel actually streamed (the step's block tables, null page
        included).  Dirty pages *outside* the kernel's coverage keep the
        probe — their faults are invisible to this step's reads but were
        reported by an earlier kernel, and the old path scrubbed them too.

        One deliberate divergence from the probe: a fault landing exactly
        in the slot this step's new K/V write overwrites is healed by the
        write itself before the kernel reads — never consumed, never
        resident afterwards, never counted.  The probe (which ran before
        the write) counted it.  Repairing only what a read would consume
        is the paper's thesis; the probe was strictly more conservative.

        ``defer`` is the desynchronized engine's attribution queue: instead
        of blocking twice on ``stats["events"]`` to charge the per-page
        ledger, the scrub's event delta stays a device scalar and is
        appended as ``(faulty_pages, delta)`` for the *next* drain to
        resolve — the drain-time scrub itself then costs zero extra host
        syncs.
        """
        scope = serving_scope(self.cfg.repair)
        if scope == "none":
            return stats
        with TraceAnnotation("engine.repair", pages=len(covered)):
            counts = np.asarray(page_counts)
            faulty = [int(p) for p in np.nonzero(counts > 0)[0]]
            stale = self._dirty - set(covered)
            if stale:
                faulty = sorted(set(faulty) | set(
                    self.pool._probe_fatal_pages(stale, read=self._read)
                ))
            return self._scrub_faulty(scope, faulty, stats, defer=defer)

    def _scrub_faulty(
        self,
        scope: str,
        faulty: Sequence[int],
        stats: stats_lib.Stats,
        defer: Optional[List] = None,
    ) -> stats_lib.Stats:
        """Shared tail of the probe- and kernel-driven reactive passes:
        scrub faulty ∪ dirty, clear the dirty set, attribute events."""
        scrub_set = sorted(set(faulty) | self._dirty)
        self._dirty.clear()
        if not scrub_set:
            return stats
        events0 = stats["events"]
        if defer is None:
            events0 = int(self._read(events0))
        stats = self.pool.scrub_scope(
            scope, scrub_set, stats, trigger="reactive"
        )
        self.n_reactive_scrubs += 1
        # the ledger charges only pages that actually held a fatal lane —
        # dirty-but-clean pages (kernel routing false positives) stay clean
        if defer is not None:
            defer.append((list(faulty), stats["events"] - events0))
            return stats
        delta = int(self._read(stats["events"])) - events0
        if delta > 0:
            self.pool.attribute(faulty, delta)
        return stats

    # ----------------------------------------------------------------- sweep
    def sweep_step(self, t: int, stats: stats_lib.Stats) -> stats_lib.Stats:
        """Background low-rate sweep tick.  Scope comes from the planner
        (page mode sweeps a rotating window; whole mode's interval scrub IS
        a whole-cache pass, matching the legacy schedule)."""
        scope = serving_scope(self.cfg.repair)
        if scope == "none" or not self.sweep.due(t):
            return stats
        if scope == "tree":
            self.n_sweep_scrubs += 1
            return self.pool.scrub_scope(scope, (), stats, trigger="interval")
        n = self.pool.cfg.n_pages
        window: List[int] = [
            (self._sweep_cursor + i) % n
            for i in range(min(self.cfg.sweep_pages, n))
        ]
        self._sweep_cursor = (self._sweep_cursor + len(window)) % n
        self.n_sweep_scrubs += 1
        return self.pool.scrub_scope(scope, window, stats, trigger="interval")

    # ------------------------------------------------------------------ intro
    def summary(self) -> dict:
        return {
            "reactive_scrubs": self.n_reactive_scrubs,
            "sweep_scrubs": self.n_sweep_scrubs,
            "scrub_calls": self.pool.scrub_calls,
            "scrubbed_bytes": self.pool.scrubbed_bytes,
            "hot_pages": int(np.count_nonzero(self.pool.page_events)),
        }
