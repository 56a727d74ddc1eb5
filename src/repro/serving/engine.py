"""`Engine` — continuous batching over the paged approximate-memory KV pool.

The facade every later scaling PR (sharded pools, async decode, multi-tenant
QoS) builds on:

    engine = Engine(model, params, ServingConfig(...))
    rid = engine.add_request(prompt_ids, max_new=32)
    while engine.has_work:
        out = engine.step()          # {"emitted": {rid: [tok]}, "finished"}
    engine.results[rid]["tokens"]    # prompt + generated

One engine step is: (1) one approximate-memory window strikes the resident
pool (simulation boundary, ``ber > 0`` only); (2) admission (a swapped-out
request skips prefill entirely and has its parked KV written back from the
host tier instead); (3) the prefill lane — fused (one prompt chunk per
mid-prefill request through the chunked-q paged kernel, straight off the
pool) or the gathered fallback (one whole-prompt ``Model.prefill`` call per
admission); (4) one jitted decode step over the static slot batch
(per-request positions — requests at different depths share the executable)
plus the reactive repair pass; (5) the background sweep tick.  With
``prefill_chunk > 0`` stages (3) and (4) coexist: prompt chunks and decode
tokens share the batch step, vllm-style.  All repair/flip/kernel events
land in the engine's unified stats stream.

Both lifecycle halves run *straight off the pool* whenever the model and
the pool rules allow it (``_paged_decode_plan``): the Pallas paged kernel
family consumes the page-major pool leaves + block tables directly,
repairing fatal KV lanes in VMEM as it streams them and emitting per-page
fatal counts — the fused kernels ARE the reactive detector, so admission,
prefill and decode together issue zero full-view ``gather``/``scatter``
copies (the surviving writes are the per-chunk/per-token K/V page slots)
and the reactive scrub runs *after* each lane from the kernels' counts.
Wide block tables additionally split the decode page walk across grid
cells (``ServingConfig.split_k`` — flash-decoding with a log-sum-exp merge).
Ineligible configurations (register-mode model reads, non-constant fills,
``repair="off"``) keep the PR-2 gathered-view path with its probe-based
pre-compute repair — token outputs are identical where both paths apply
(bit-exact for f32 pools; bf16 pools quantize softmax weights before the
online-softmax rescale, so parity there is value-approximate, token-level
in practice).

Static shapes: the decode batch is always ``(max_batch, 1)`` tokens over
``(max_batch, max_pages_per_request)`` block tables (empty slots run the
null page at position 0 and are ignored), so the whole serving run compiles
exactly one decode executable; prefill compiles one executable per distinct
chunk width (a fixed ``prefill_chunk`` means one compiled prefill step for
the whole run; 0 retraces per distinct remaining-prompt length, like the
gathered path).

Two serving-scale mechanisms ride the same fused path (README §Serving
engine — "Sharded decode & load testing"):

* **Device-local sharded walk** — when the engine's space carries a mesh
  and the pool's page axis is genuinely sharded over one mesh axis
  (``pool.page_shard_axis()``), the fused decode/prefill executables run
  the kernels under ``shard_map``: each device walks only the block-table
  slots whose pages it owns, repairs them in its own VMEM, and the partial
  softmax states merge with one ``all_gather`` + log-sum-exp combine.  No
  KV page ever crosses a device boundary.  Indivisible pool geometries
  degrade to the single-device walk transparently.

* **Desynchronized stats drain** — ``ServingConfig.drain_interval > 0``
  keeps the kernels' per-page fatal counts resident on device,
  accumulating across steps; every N steps one readback drains them and
  the reactive scrub covers the union of flagged pages.  The fused kernels
  repair on read with a value-independent fill, so deferring the HBM
  scrub never changes the tokens.  ``Engine.metrics()`` reports
  ``n_host_syncs`` — the blocking device→host readback count the drain
  exists to shrink.  Each readback is also a profiler span
  (``engine.readback``), as is every stage of a step (``Engine.step``).

``launch.serve.generate(..., paged=True)`` is the single-request degenerate
case of this engine.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..core import stats as stats_lib
from ..core.regions import Region
from ..kernels import common as kernels_common
from ..launch.serve import build_serve_step
from ..runtime import ApproxSpace, ScrubSchedule
from ..runtime.plan import serving_scope
from .config import ServingConfig
from .pool import PagedKVPool
from .prefix_cache import PrefixCache
from .repair import PageRepairManager
from .scheduler import Request, RequestState, Scheduler
from .tiers import TierManager


# The pool's range guard: K/V lanes of magnitude >= 2**32 are fatal too.
# A flip of one of the two top exponent bits multiplies a lane by 2**64 or
# 2**128 — a legal float that NaN/Inf detection cannot see, and one that
# overflows the f32 score and RMSNorm reductions downstream into a
# non-finite readout.  Every such flip of a lane above 2**-32 lands at or
# past the guard, while real K/V lanes stay orders of magnitude below it
# (the same models serve in float16, whose largest value is 65504).
KV_RANGE_GUARD = 2.0 ** 32


def engine_space(model: Any, *, mesh: Any = None) -> ApproxSpace:
    """The engine's default runtime: memory-forced, NaN/Inf plus the
    ``KV_RANGE_GUARD`` range guard, no boundary scrub (the page repair
    manager owns every scrub), private to this engine so stats streams stay
    isolated.  ``mesh`` places it on a device mesh under the default
    sharding rules (the pool's pages shard over "data" — the device-local
    sharded walk).

    The default fill is ZERO (not the training default ``neighbor_mean``):
    KV lanes have no cheap neighborhood statistic on the decode hot path,
    zero is the paper's fix-to-a-predetermined-value choice, and a
    value-independent fill is what lets the fused paged-attention kernel
    apply the exact same repair in VMEM that the pool scrub applies in HBM
    — the fused decode path stays bit-compatible with the gathered one.  A
    model config carrying an explicit ``RuleSet`` keeps it (per-path rules
    already say how cache leaves are protected; eligibility then decides
    fused vs fallback)."""
    return ApproxSpace(
        model.cfg.repair,
        mesh=mesh,
        mode="memory",
        policy="zero",
        max_magnitude=KV_RANGE_GUARD,
        scrub=ScrubSchedule(boundary=False, interval=0),
    )


def _readout(nxt, rows, keep_rows: bool):
    """A step's readout as ONE (2, B) int32 array — greedy tokens over a
    per-row non-finite flag, so the engine counts NaN/Inf logits rows in
    the same readback that fetches the tokens — plus the f32 logits rows
    themselves when the engine records them (``None`` otherwise)."""
    bad = ~jnp.all(jnp.isfinite(rows), axis=-1)
    out = jnp.stack([nxt, bad.astype(jnp.int32)])
    return out, (rows.astype(jnp.float32) if keep_rows else None)


# ---------------------------------------------------------------------------
# Paged-decode eligibility.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _PagedDecodePlan:
    """Static repair spec the fused decode step is compiled against: one
    detector per pool-leaf name (``None`` = detection off for that leaf)
    plus one ``(policy, constant)`` kernel fill per leaf name — each
    operand's tile repairs with its own rule's fill, so a mixed-fill
    RuleSet no longer forces the gathered-decode fallback.  ``prefill``
    extends the same spec to admission: the chunked-q paged prefill kernel
    runs with identical per-operand detectors/fills, so the whole request
    lifecycle shares one repair contract."""

    detectors: Mapping[str, Any]
    fills: Mapping[str, Tuple[str, float]]
    prefill: bool = False


def _paged_decode_plan(
    model: Any, space: ApproxSpace, pool: PagedKVPool, cfg: ServingConfig
) -> Optional[_PagedDecodePlan]:
    """The fused-decode spec, or ``None`` when the configuration must keep
    the gathered-view fallback: no paged decode path on the model,
    ``repair="off"`` (the fused kernel always repairs what it reads — "no
    repair" semantics need the plain path), register-mode model reads (the
    in-kernel repair replaces ``use()``-site repair, not both), a fill the
    kernel cannot reproduce bit-for-bit, or a detector that does not encode
    into the scalar-prefetch constants (>32-bit dtypes)."""
    if not getattr(model, "supports_paged_decode", False):
        return None
    if serving_scope(cfg.repair) == "none" or space.config.mode != "memory":
        return None
    if getattr(model.cfg.repair, "mode", "off") == "register":
        return None
    regions = space.regions_for(pool.tree)
    rule_tree, _ = space.rules_for(pool.tree)
    flat = jax.tree_util.tree_flatten_with_path(pool.tree)[0]
    detectors: Dict[str, Any] = {}
    fills: Dict[str, Tuple[str, float]] = {}
    for (path, leaf), region, rule in zip(
        flat, jax.tree.leaves(regions), jax.tree.leaves(rule_tree)
    ):
        name = str(getattr(path[-1], "key", path[-1]))
        is_float = hasattr(leaf, "dtype") and jnp.issubdtype(
            leaf.dtype, jnp.floating
        )
        if (
            not is_float
            or region is not Region.APPROX
            or not rule.fires("reactive")
        ):
            det = None          # probe-gate parity: this leaf is never probed
            fill = ("zero", 0.0)     # irrelevant: nothing is ever detected
        else:
            fill = kernels_common.kernel_fill(rule.fill)
            if fill is None:
                return None
            try:
                rule.detect.constants(leaf.dtype)
            except (TypeError, ValueError):
                return None
            det = rule.detect
        if name in detectors and detectors[name] != det:
            return None         # one detector per leaf name (kernel operand)
        if det is not None and fills.get(name, fill) != fill:
            return None         # one fill per leaf name (kernel operand)
        detectors[name] = det
        if det is not None or name not in fills:
            fills[name] = fill
    return _PagedDecodePlan(
        detectors=detectors,
        fills=fills,
        # the prefill arm rides on decode eligibility: same pool rules, same
        # kernel repair contract — only the model surface and the config
        # switch are extra
        prefill=(
            bool(getattr(model, "supports_paged_prefill", False))
            and cfg.paged_prefill == "auto"
        ),
    )


class Engine:
    """Continuous-batching serving engine (add_request / step / run)."""

    def __init__(
        self,
        model: Any,
        params: Any,
        cfg: Optional[ServingConfig] = None,
        space: Optional[ApproxSpace] = None,
    ):
        if not model.supports_paged_kv:
            raise NotImplementedError(
                f"{type(model).__name__} has no paged KV layout — the engine "
                "serves attention-cache architectures"
            )
        if not model.supports_batched_prefill:
            raise NotImplementedError(
                f"{type(model).__name__} cannot batched-prefill — the engine "
                "consumes whole prompts in one pass"
            )
        self.model = model
        self.cfg = cfg or ServingConfig()
        self.space = space or engine_space(model)
        # mesh-native serving (ROADMAP leftover): when the engine's space
        # carries a mesh, model params are device_put onto their logical-axis
        # shardings — the same `serve_shardings` placement jit_serve_step
        # uses — instead of staying replicated alongside the sharded pool.
        self.params_shardings = None
        if self.space.mesh is not None:
            from ..distributed import sharding as sh  # deferred: keep layering thin

            rules = self.space.rules or sh.rules_for_mesh(self.space.mesh)
            self.params_shardings = sh.tree_shardings(
                model.abstract_params(), model.logical_axes(),
                self.space.mesh, rules,
            )
            params = jax.device_put(params, self.params_shardings)
        self.params = params
        self.pool = PagedKVPool(model, self.space, self.cfg)
        # observation counters the hot path reports through (must exist
        # before any helper that syncs is first called)
        self.n_host_syncs = 0
        # decode walk coverage: the context pages of every decode batch's
        # requests, and the B x M block-table slots those batches carried
        self.decode_pages_walked = 0
        self.decode_page_slots = 0
        # device-local sharded hot path: engaged only when the pool's page
        # axis is genuinely sharded over exactly one mesh axis (divisible
        # row count) — otherwise the single-device kernel walk stays
        axis = self.pool.page_shard_axis()
        self._kernel_shard = (
            (self.space.mesh, axis) if axis is not None else None
        )
        # tiered KV (README §Serving engine — "Tiered KV"): a host-memory
        # exact tier preemption swaps to (boundary scrub on the way out)
        # and prefix-cache eviction demotes into
        self.tiers = (
            TierManager(self.pool, self.space, self.cfg)
            if self.cfg.host_pages > 0 else None
        )
        self.cache = (
            PrefixCache(self.pool, self.space, self.cfg, tiers=self.tiers)
            if self.cfg.prefix_cache else None
        )
        self.sched = Scheduler(
            self.pool, self.cfg, cache=self.cache, tiers=self.tiers
        )
        self.repair = PageRepairManager(
            self.pool, self.space, self.cfg,
            readback=self._host,
        )
        # the one greedy step builder (shared with launch.serve.generate, so
        # the engine-vs-generate token-parity contract cannot drift)
        serve_step = self.space.wrap_serve_step(build_serve_step(model))
        keep = self.cfg.record_logits

        def gathered_step(params, view, batch, pos, stats):
            nxt, logits, view, stats = serve_step(params, view, batch, pos, stats)
            out, rows = _readout(nxt, logits[:, -1, :], keep)
            return out, rows, view, stats

        self._step_fn = jax.jit(gathered_step)
        # fused paged decode: compiled once against the pool rules' static
        # repair spec; None keeps the gathered-view fallback
        self.paged_plan = (
            _paged_decode_plan(model, self.space, self.pool, self.cfg)
            if self.cfg.paged_decode == "auto" else None
        )
        # split-K flash decoding: resolved once against the static block-
        # table width (a divisor of it — see ServingConfig.resolve_split_k)
        self._split_k = self.cfg.resolve_split_k()
        self._paged_fn = (
            self._build_paged_step(self.paged_plan)
            if self.paged_plan is not None else None
        )
        # fused chunked prefill: the admission-side twin of the decode step
        self._prefill_fn = (
            self._build_paged_prefill_step(self.paged_plan)
            if self.paged_plan is not None and self.paged_plan.prefill
            else None
        )
        self._prefilling: List[Request] = []   # mid-prefill (chunk) lane
        self.kernel_counts = np.zeros(8, np.int64)   # fused AT_* totals
        # generated tokens whose readout logits held a NaN/Inf, on every
        # path — what repair exists to keep at zero
        self.nonfinite_rows = 0
        # rid -> recorded readout logits rows (ServingConfig.record_logits)
        self._logits: Dict[int, List[np.ndarray]] = {}
        # desynchronized stats drain (drain_interval > 0): fused-lane
        # counters accumulate on device; one concatenated readback per
        # drain window feeds the reactive scrub
        self._desync = (
            self.cfg.drain_interval > 0 and self._paged_fn is not None
        )
        self._pending = None            # device (n_pages+1+8,) accumulator
        self._pending_covered: set = set()
        self._pending_attr: List[Tuple[List[int], Any]] = []
        self._steps_since_drain = 0
        self._stream = stats_lib.zeros()
        self._requests: Dict[int, Request] = {}
        self.results: Dict[int, Dict[str, Any]] = {}
        self._next_rid = 0
        self._t = 0
        self._inject_key = jax.random.PRNGKey(self.cfg.seed + 1)
        self._last_touched: List[int] = []
        self.tokens_emitted = 0
        self.prefill_tokens_saved = 0
        # tokens a re-prefill had to re-process after a recompute-style
        # preemption (the cost swap-out exists to avoid)
        self.prefill_tokens_recomputed = 0
        # online autopilot guard (README §Autopilot): per-window fault
        # monitor over the pool rules; a trip tightens the drifting group's
        # rule and rebuilds the fused executables that closed over it
        self.guard = None
        self.autopilot_trips = 0
        if self.cfg.autopilot is not None:
            from ..autopilot.guard import OnlineGuard  # deferred import
            self.guard = OnlineGuard(self.space, self.cfg.autopilot)

    # ------------------------------------------------------------------ admit
    def add_request(self, prompt: Sequence[int], max_new: int) -> int:
        """Queue one generation request; returns its id."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=int(max_new))
        self._requests[rid] = req
        self.sched.add(req)
        return rid

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    # ------------------------------------------------------------------- step
    def step(self) -> Dict[str, Any]:
        """One engine step; returns the tokens emitted and requests finished.

        The step is the profiler span ``engine.step`` (``step_num`` = the
        step index) and each stage below a child span — ``engine.drain``,
        ``engine.admit``, ``engine.prefill``, ``engine.decode``,
        ``engine.sweep``, ``engine.guard`` — with ``engine.prefill_chunk``,
        ``engine.repair``, ``engine.readback`` and ``pool.reset_pages``
        beneath them, all on the profiler's clock (README §Serving engine —
        "Profiler spans")."""
        with StepTraceAnnotation("engine.step", step_num=self._t):
            return self._step()

    def _step(self) -> Dict[str, Any]:
        t = self._t
        self.pool.now = t        # dwell clock: one step = one fault window
        emitted: Dict[int, List[int]] = {}
        finished: List[int] = []
        # kernel-counter routing targets the pages THIS step touches; stale
        # entries could point at pages since freed and reallocated
        self._last_touched = []

        # (0) deferred stats drain: runs BEFORE this step's flips land, so
        # a drain_interval=1 engine scrubs exactly the pages the lockstep
        # engine scrubbed inside the previous step — the pool bits entering
        # stage (1) are identical and the token trajectory replays
        if self._desync and self._steps_since_drain >= self.cfg.drain_interval:
            with TraceAnnotation("engine.drain"):
                self._drain_pending()

        # (1) simulation boundary: one window of flips strikes the pool —
        # the same stats-threading injection entry point the train loop's
        # inject_state uses (flips land in the engine's functional stream,
        # donated pool buffers, compiled per pool layout)
        if self.cfg.ber > 0.0:
            self._inject_key, k = jax.random.split(self._inject_key)
            self.pool.tree, self._stream = self.space.inject(
                self.pool.tree, k, self.cfg.ber,
                stats=self._stream, donate=True,
            )

        # (2) admission.  A preempted lane member leaves the lane here: a
        # recompute victim restarts from scratch when re-admitted, a swap
        # victim rejoins the lane at its saved chunk position on swap-in.
        # (On the gathered fallback the whole-prompt prefill rides inside
        # admission, so its spans nest in ``engine.admit``.)
        with TraceAnnotation("engine.admit") as span:
            plan = self._admit(emitted, finished)
            span.set_metadata(admitted=len(plan.admitted))

        # (3) the fused prefill lane: one prompt chunk per mid-prefill
        # request, straight off the pool, then ONE reactive pass from the
        # summed per-page fatal counts (per-request passes would scrub a
        # faulty shared/null page once per request — the gathered path
        # charges it once per step).  The counter vectors stay on device
        # through the lane; `_flush_lane` reads them back (lockstep) or
        # parks them in the pending accumulator (desync).
        if self._prefilling:
            with TraceAnnotation(
                "engine.prefill", requests=len(self._prefilling)
            ):
                self._prefill_lane(emitted, finished)

        # (4) one decode step + the reactive repair pass.
        if plan.decode:
            with TraceAnnotation("engine.decode") as span:
                n = self._decode_stage(plan.decode, emitted, finished)
                span.set_metadata(batch=n)

        # (5) background sweep tick
        with TraceAnnotation("engine.sweep"):
            self._stream = self.repair.sweep_step(t, self._stream)

        # (6) autopilot guard: close the observation window
        if self.guard is not None:
            with TraceAnnotation("engine.guard"):
                self._guard_tick()

        if self._desync:
            self._steps_since_drain += 1
        self._t += 1
        for rid, toks in emitted.items():
            self.tokens_emitted += len(toks)
        return {"t": t, "emitted": emitted, "finished": finished}

    def _admit(
        self, emitted: Dict[int, List[int]], finished: List[int]
    ) -> Any:
        """Stage (2): the scheduler's plan for this step, page allocation,
        prefix-cache hits, swap-ins and (gathered fallback) whole-prompt
        prefills.  Returns the plan."""
        self._prefilling = [
            r for r in self._prefilling if r.state is RequestState.RUNNING
        ]
        plan = self.sched.step_plan(self._prefilling)
        admitted = plan.admitted
        if admitted:
            pages = sorted({p for r in admitted for p in r.pages})
            shared = {
                e.page
                for r in admitted if r.cache_hit is not None
                for e in r.cache_hit.full
            } | {
                r.cache_hit.partial.page
                for r in admitted
                if r.cache_hit is not None and r.cache_hit.partial is not None
            }
            # swapped-in pages are excluded too: they are about to be
            # overwritten by exact host-tier bits (probing the just-zeroed
            # allocation would be charging for nothing)
            swapped = {p for r in admitted if r.swap is not None for p in r.pages}
            fresh = sorted(set(pages) - shared - swapped)
            if fresh and self._prefill_fn is None:
                # gathered fallback only: admitted pages are freshly zeroed,
                # but the null padding page rides along — one probe pass
                # covers every admission before prefill consumes its pages.
                # Cache-hit shared pages are excluded: their admission
                # policy IS scrub-on-reuse.  On the fused path the prefill
                # kernel is the detector — no probe at all.
                self._stream = self.repair.repair_step(fresh, self._stream)
            self._last_touched = pages
        for req in admitted:
            if req.swap is not None:
                # tier swap-in instead of re-prefill: the parked context is
                # written back whole and the request decodes this very step
                # — unless it was swapped out mid-prefill, in which case it
                # rejoins the chunk lane where it left off
                handle, req.swap = req.swap, None
                self.tiers.swap_in(handle, req.pages)
                if req.prefill_pos is not None and self._prefill_fn is not None:
                    self._prefilling.append(req)
                continue
            if self.cache is not None:
                self._stream = self.cache.prepare_hit(req, self._stream)
            if self._prefill_fn is not None:
                # fused lane: the request streams prompt chunks over the
                # next step(s); cache insert + finish happen when the last
                # chunk lands
                if req.prefill_pos is None:
                    req.prefill_pos = 0
                self._prefilling.append(req)
                continue
            self._prefill(req, emitted)
            if self.cache is not None:
                # insert BEFORE finish: the cache's own references keep the
                # prefix resident even when the request finishes right away
                self.cache.insert(req)
            if req.state is RequestState.RUNNING and self._maybe_finish(req):
                finished.append(req.rid)
        return plan

    def _prefill_lane(
        self, emitted: Dict[int, List[int]], finished: List[int]
    ) -> None:
        """Stage (3): one chunk per mid-prefill request, then the lane's
        reactive pass."""
        page_counts = counts = None
        covered = {self.pool.null_page}
        still: List[Request] = []
        for req in self._prefilling:
            pc_r, cnt_r, done = self._prefill_paged(req, emitted)
            page_counts = pc_r if page_counts is None else page_counts + pc_r
            counts = cnt_r if counts is None else counts + cnt_r
            covered.update(req.pages)
            if not done:
                still.append(req)
                continue
            if self.cache is not None:
                self.cache.insert(req)
            if req.state is RequestState.RUNNING and self._maybe_finish(req):
                finished.append(req.rid)
        self._prefilling = still
        self._last_touched = sorted(
            set(self._last_touched) | (covered - {self.pool.null_page})
        )
        self._flush_lane(page_counts, counts, covered)

    def _decode_stage(
        self,
        planned: List[Request],
        emitted: Dict[int, List[int]],
        finished: List[int],
    ) -> int:
        """Stage (4): reserve each planned request's next page, one decode
        step over those still running, the reactive repair pass.  Reserving
        a page for one request may preempt another — both one that hasn't
        reserved yet (inner state check) and one that already did (final
        filter): victims never reach the decode batch.  Returns the batch
        size."""
        decodable = []
        for r in planned:
            if r.state is not RequestState.RUNNING:
                continue
            if self._reserve_next_page(r):
                decodable.append(r)
        decodable = [r for r in decodable if r.state is RequestState.RUNNING]
        if not decodable:
            return 0
        touched = sorted(
            set(self._last_touched)
            | {p for r in decodable for p in r.pages}
        )
        self._last_touched = touched
        if self._paged_fn is not None:
            # fused path: the kernel repairs fatal lanes on read and IS
            # the detector — decode first, then scrub the resident pool
            # pages its per-page counts flagged (reactive write-back)
            page_counts, counts = self._decode_paged(decodable, emitted)
            self._flush_lane(
                page_counts, counts, set(touched) | {self.pool.null_page}
            )
        else:
            self._stream = self.repair.repair_step(touched, self._stream)
            self._decode(decodable, emitted)
        for req in decodable:
            if self._maybe_finish(req):
                finished.append(req.rid)
        return len(decodable)

    def _guard_tick(self) -> None:
        """Stage (6): close the guard's observation window.  A trip swapped
        the pool RuleSet, so the fused executables that closed over the old
        rules' detectors/fills must be rebuilt (the gathered _step_fn is
        rules-independent — the engine space never scrubs in-step)."""
        decisions = self.guard.tick()
        if not decisions:
            return
        self.autopilot_trips += len(decisions)
        self.paged_plan = (
            _paged_decode_plan(self.model, self.space, self.pool, self.cfg)
            if self.cfg.paged_decode == "auto" else None
        )
        self._paged_fn = (
            self._build_paged_step(self.paged_plan)
            if self.paged_plan is not None else None
        )
        self._prefill_fn = (
            self._build_paged_prefill_step(self.paged_plan)
            if self.paged_plan is not None and self.paged_plan.prefill
            else None
        )
        # a trip may have forced the gathered fallback — flush any
        # deferred counters before the fused path goes away
        self._desync = (
            self.cfg.drain_interval > 0 and self._paged_fn is not None
        )
        if not self._desync:
            self.drain()

    def run(self, max_idle_steps: int = 100) -> Dict[int, Dict[str, Any]]:
        """Drive the engine until every queued request finishes.  Long
        workloads run as many steps as they need; the guard fires only on
        genuine stalls (``max_idle_steps`` consecutive steps emitting and
        finishing nothing)."""
        idle = 0
        while self.has_work:
            out = self.step()
            idle = 0 if (out["emitted"] or out["finished"]) else idle + 1
            if idle > max_idle_steps:
                raise RuntimeError(
                    f"engine made no progress in {max_idle_steps} steps"
                )
        self.drain()        # park nothing: scrub what the last window flagged
        return self.results

    # ----------------------------------------------------- stats drain
    def _host(self, x) -> np.ndarray:
        """Blocking device→host readback — every hot-path sync funnels
        through here (the repair manager's too) so
        ``metrics()["n_host_syncs"]`` audits them all, and each is one
        ``engine.readback`` profiler span."""
        with TraceAnnotation("engine.readback"):
            self.n_host_syncs += 1
            return np.asarray(x)

    def _flush_lane(self, page_counts, counts, covered) -> None:
        """One fused lane's kernel counters.  Lockstep: read both vectors
        back now and run the reactive pass.  Desync: fold them into the
        resident pending accumulator — ONE concatenated device array, so a
        later drain costs a single readback no matter how many lanes and
        steps it covers."""
        if page_counts is None:
            return
        if self._desync:
            pending = jnp.concatenate(
                [jnp.asarray(page_counts, jnp.int32),
                 jnp.asarray(counts, jnp.int32)]
            )
            self._pending = (
                pending if self._pending is None else self._pending + pending
            )
            self._pending_covered |= set(covered)
            return
        pc = self._host(page_counts)
        self.kernel_counts += self._host(counts).astype(np.int64)
        self._stream = self.repair.repair_counts(pc, covered, self._stream)

    def _resolve_attr(self) -> None:
        """Charge the per-page ledger with the event deltas a drain-time
        scrub deferred (device scalars by now long computed)."""
        attrs, self._pending_attr = self._pending_attr, []
        for pages, delta in attrs:
            d = int(self._host(delta))
            if d > 0:
                self.pool.attribute(pages, d)

    def _drain_pending(self) -> None:
        """One deferred drain: resolve the previous drain's attribution,
        read the whole pending accumulator back in ONE sync, and scrub the
        union of flagged pages (its own attribution deferred in turn)."""
        self._resolve_attr()
        self._steps_since_drain = 0
        if self._pending is None:
            return
        pend = self._host(self._pending)
        n_rows = self.cfg.n_pages + 1
        page_counts, counts = pend[:n_rows], pend[n_rows:]
        self.kernel_counts += counts.astype(np.int64)
        covered = self._pending_covered
        self._pending = None
        self._pending_covered = set()
        self._stream = self.repair.repair_counts(
            page_counts, covered, self._stream, defer=self._pending_attr
        )

    def drain(self) -> None:
        """Flush every deferred readback: the pending kernel counters, the
        reactive scrub they drive, and that scrub's ledger attribution.
        ``metrics()`` and the end of ``run()`` call this; a lockstep
        (``drain_interval == 0``) engine no-ops."""
        self._drain_pending()
        self._resolve_attr()

    # -------------------------------------------------------------- internals
    def _build_paged_step(self, spec: _PagedDecodePlan):
        """The fused decode executable: model paged step + greedy readout +
        per-page fatal counts scatter-added over the block tables.  The pool
        tree is donated — the in-place write-back of the one resident."""
        model, n_rows = self.model, self.cfg.n_pages + 1
        split_k = self._split_k
        shard = self._kernel_shard
        keep = self.cfg.record_logits

        def paged_step(params, pool_tree, batch, bt, pos, stats):
            logits, pool_tree, slot_counts, counts = model.serve_step_paged(
                params, pool_tree, batch, bt, pos,
                detectors=spec.detectors, fills=spec.fills, split_k=split_k,
                shard=shard,
            )
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            out, rows = _readout(nxt, logits[:, -1, :], keep)
            page_counts = jnp.zeros((n_rows,), jnp.int32).at[bt].add(
                slot_counts
            )
            return out, rows, pool_tree, page_counts, counts, stats

        return jax.jit(paged_step, donate_argnums=(1,))

    def _build_paged_prefill_step(self, spec: _PagedDecodePlan):
        """The fused prefill executable: chunked-q paged prefill + greedy
        readout at the chunk's last valid row + per-page fatal counts
        scatter-added over the block table.  One compiled executable per
        distinct chunk width (``q_len`` is a traced operand — ragged tails
        share the executable with full chunks)."""
        model, n_rows = self.model, self.cfg.n_pages + 1
        shard = self._kernel_shard
        keep = self.cfg.record_logits

        def prefill_step(params, pool_tree, batch, bt, q_start, q_len, stats):
            logits, pool_tree, slot_counts, counts = model.prefill_paged(
                params, pool_tree, batch, bt, q_start, q_len,
                detectors=spec.detectors, fills=spec.fills,
                shard=shard,
            )
            last = jnp.maximum(q_len - 1, 0)
            readout = jnp.take_along_axis(
                logits, last[:, None, None], axis=1
            )[:, 0]
            nxt = jnp.argmax(readout, axis=-1).astype(jnp.int32)
            out, rows = _readout(nxt, readout, keep)
            page_counts = jnp.zeros((n_rows,), jnp.int32).at[bt].add(
                slot_counts
            )
            return out, rows, pool_tree, page_counts, counts, stats

        return jax.jit(prefill_step, donate_argnums=(1,))

    def _reserve_next_page(self, req: Request) -> bool:
        """Point ``req.pos`` at this step's write position and make sure its
        block table covers it (growing/preempting under page pressure)."""
        req.pos = req.n_context - 1
        return self.sched.ensure_capacity(req)

    def _prefill(self, req: Request, emitted: Dict[int, List[int]]) -> None:
        """One batched prefill: the (re-)prefill context in one
        ``Model.prefill`` call over the request's gathered pages.  A cache
        hit prefills only the *suffix* — the matched prefix's KV is already
        resident in the shared (and CoW-forked) pages, so the pass starts
        at cache position ``req.cached_tokens``."""
        toks = req.prefill_tokens()
        n_cached = req.cached_tokens
        with TraceAnnotation("engine.prefill_chunk", rid=req.rid,
                             q_start=n_cached, q_len=len(toks) - n_cached):
            bt = self.pool.block_table(req.pages)[None, :]
            view = self.pool.gather(bt)
            tokens = jnp.asarray([toks[n_cached:]], jnp.int32)
            out, rows, view, self._stream = self._step_fn(
                self.params, view, {"tokens": tokens},
                jnp.asarray(n_cached, jnp.int32), self._stream,
            )
            self.pool.scatter(view, bt)
            req.pos = len(toks)
            self.prefill_tokens_saved += n_cached
            if req.n_preempted:
                # every non-cached token of a post-preemption re-prefill is
                # work the engine already did once — the recompute bill the
                # tier swap exists to avoid
                self.prefill_tokens_recomputed += len(toks) - n_cached
            self._emit([req], out, rows, emitted, slots=[0])

    def _prefill_paged(
        self, req: Request, emitted: Dict[int, List[int]]
    ) -> Tuple[jax.Array, jax.Array, bool]:
        """One fused prompt chunk straight off the pool: write the chunk's
        K/V into the request's pages and attend via the chunked-q paged
        kernel — zero full-view copies.  ``prefill_chunk == 0`` consumes
        the whole remaining context in one chunk.  Returns the kernel's
        per-page fatal counts and AT_* counter vector as DEVICE arrays
        (the caller's lane flush decides when to read them back), plus
        whether the prefill completed (the first generated token is
        emitted only then — greedy readout at the last prompt position,
        same as the gathered path)."""
        toks = req.prefill_tokens()
        start = req.cached_tokens + req.prefill_pos
        rest = toks[start:]
        # static chunk width: a short tail pads up rather than retracing
        width = len(rest) if self.cfg.prefill_chunk == 0 else self.cfg.prefill_chunk
        chunk = rest[:width]
        q_len = len(chunk)
        padded = chunk + [0] * (width - q_len)
        with TraceAnnotation("engine.prefill_chunk", rid=req.rid,
                             q_start=start, q_len=q_len):
            bt = self.pool.block_table(req.pages)[None, :]
            out, rows, self.pool.tree, page_counts, counts, self._stream = (
                self._prefill_fn(
                    self.params, self.pool.tree,
                    {"tokens": jnp.asarray([padded], jnp.int32)},
                    jnp.asarray(bt), jnp.asarray([start], jnp.int32),
                    jnp.asarray([q_len], jnp.int32), self._stream,
                )
            )
            req.prefill_pos += q_len
            done = start + q_len >= len(toks)
            if done:
                req.pos = len(toks)
                req.prefill_pos = None
                self.prefill_tokens_saved += req.cached_tokens
                if req.n_preempted:
                    self.prefill_tokens_recomputed += (
                        len(toks) - req.cached_tokens
                    )
                self._emit([req], out, rows, emitted, slots=[0])
        return page_counts, counts, done

    def _decode_batch(
        self, reqs: List[Request]
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The static-shape decode batch: block tables, tokens, positions.
        Counts the pages the decode walk reads (a request's positions
        ``0..pos``) against the table slots it carries."""
        B, M = self.cfg.max_batch, self.cfg.max_pages_per_request
        bt = np.full((B, M), self.pool.null_page, np.int32)
        tokens = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        for req in reqs:
            bt[req.slot] = self.pool.block_table(req.pages)
            tokens[req.slot, 0] = req.last_token
            pos[req.slot] = req.pos
            self.decode_pages_walked += self.cfg.pages_for(req.pos + 1)
        self.decode_page_slots += B * M
        return bt, tokens, pos

    def _emit(self, reqs, out, rows, emitted, slots=None) -> None:
        """Append each request's greedy token from its readout row (its
        decode slot, or ``slots``) — one readback of the step's
        ``_readout``, a second for the logits rows when recorded — and
        count non-finite readout rows."""
        out = self._host(out)
        rows = self._host(rows) if rows is not None else None
        if slots is None:
            slots = [req.slot for req in reqs]
        for req, i in zip(reqs, slots):
            tok = int(out[0, i])
            self.nonfinite_rows += int(out[1, i])
            req.tokens.append(tok)
            emitted.setdefault(req.rid, []).append(tok)
            if rows is not None:
                self._logits.setdefault(req.rid, []).append(rows[i])

    def _decode(
        self, reqs: List[Request], emitted: Dict[int, List[int]]
    ) -> None:
        """Gathered-view decode (the PR-2 fallback path)."""
        bt, tokens, pos = self._decode_batch(reqs)
        view = self.pool.gather(bt)
        out, rows, view, self._stream = self._step_fn(
            self.params, view, {"tokens": jnp.asarray(tokens)},
            jnp.asarray(pos), self._stream,
        )
        self.pool.scatter(view, bt)
        self._emit(reqs, out, rows, emitted)
        for req in reqs:
            req.pos += 1

    def _decode_paged(
        self, reqs: List[Request], emitted: Dict[int, List[int]]
    ) -> Tuple[jax.Array, jax.Array]:
        """Fused decode straight off the pool: zero full-view copies.  The
        donated pool tree is replaced in place; returns the kernel's
        per-page fatal counts and AT_* counter vector as DEVICE arrays
        (the reactive detector's input — read back by the lane flush or a
        later drain, never here)."""
        bt, tokens, pos = self._decode_batch(reqs)
        out, rows, self.pool.tree, page_counts, counts, self._stream = (
            self._paged_fn(
                self.params, self.pool.tree, {"tokens": jnp.asarray(tokens)},
                jnp.asarray(bt), jnp.asarray(pos), self._stream,
            )
        )
        self._emit(reqs, out, rows, emitted)
        for req in reqs:
            req.pos += 1
        return page_counts, counts

    def _maybe_finish(self, req: Request) -> bool:
        if req.done or req.n_context >= self.cfg.max_seq:
            req.truncated = not req.done
            self.sched.finish(req)
            self.results[req.rid] = {
                "tokens": req.prompt + req.tokens,
                "generated": list(req.tokens),
                "n_preempted": req.n_preempted,
                "truncated": req.truncated,
            }
            if self.cfg.record_logits:
                self.results[req.rid]["logits"] = np.stack(
                    self._logits.pop(req.rid)
                )
            return True
        return False

    # ----------------------------------------------------------- observation
    def record_kernel(self, counts) -> None:
        """Report a fused-kernel counter vector (``kernels.ops`` int32[8]
        layout): folded into the unified stats and routed back to the pages
        the last decode step touched (they are scrubbed next repair pass)."""
        self.repair.note_kernel(counts, self._last_touched)

    def unified_stats(self) -> stats_lib.Stats:
        """The space's host-side stream (injection flips, kernel counters)
        merged with the engine's functional step stream."""
        return stats_lib.merge(self.space.stats, self._stream)

    def stats_dict(self) -> Dict[str, int]:
        return stats_lib.as_dict(self.unified_stats())

    def rule_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-rule repair counters (README §RepairRule) over every pool
        repair pass this engine ran."""
        return self.space.rule_stats()

    def cache_stats(self) -> Dict[str, Any]:
        """Prefix-cache observation counters (``{"enabled": False}`` when
        the cache is off)."""
        out: Dict[str, Any] = {
            "enabled": self.cache is not None,
            "prefill_tokens_saved": self.prefill_tokens_saved,
        }
        if self.cache is not None:
            out.update(self.cache.stats())
        return out

    def tier_stats(self) -> Dict[str, Any]:
        """Tiered-KV observation counters (``{"enabled": False}`` when
        ``host_pages == 0``): swap traffic, the per-tier boundary-scrub
        byte ledger, and how often a full host store forced the recompute
        fallback."""
        out: Dict[str, Any] = {
            "enabled": self.tiers is not None,
            "swap_policy": self.cfg.swap_policy,
            "n_swap_preemptions": self.sched.n_swap_preemptions,
            "prefill_tokens_recomputed": self.prefill_tokens_recomputed,
        }
        if self.tiers is not None:
            out.update(self.tiers.stats())
        return out

    def metrics(self) -> Dict[str, Any]:
        self.drain()        # metrics reflect a fully flushed engine
        toks = max(self.tokens_emitted, 1)
        steps = max(self._t, 1)
        return {
            "tokens_emitted": self.tokens_emitted,
            "n_host_syncs": self.n_host_syncs,
            "host_syncs_per_step": self.n_host_syncs / steps,
            "decode_pages_walked": self.decode_pages_walked,
            "decode_page_slots": self.decode_page_slots,
            "drain_interval": self.cfg.drain_interval,
            "sharded_kernels": self._kernel_shard is not None,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefill_tokens_recomputed": self.prefill_tokens_recomputed,
            "n_preemptions": self.sched.n_preemptions,
            "n_swap_preemptions": self.sched.n_swap_preemptions,
            "scrubbed_bytes": self.pool.scrubbed_bytes,
            "scrub_calls": self.pool.scrub_calls,
            "scrubbed_bytes_per_token": self.pool.scrubbed_bytes / toks,
            "paged_decode": self._paged_fn is not None,
            "paged_prefill": self._prefill_fn is not None,
            "split_k": self._split_k,
            "pool_gathers": self.pool.n_gathers,
            "pool_scatters": self.pool.n_scatters,
            "paged_kernel_events": int(self.kernel_counts[6]),  # AT_EV_TOTAL
            "nonfinite_logit_rows": self.nonfinite_rows,
            "autopilot_trips": self.autopilot_trips,
            **self.repair.summary(),
        }
