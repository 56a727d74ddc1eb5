"""`RepairPlan` — one planner for every repair pass across train / serve /
checkpoint.

Before this module the runtime had three parallel repair paths that each
re-decided what to repair and how: the train boundary scrub (whole resident
tree), the serving page scrub (rows of the pool's leading page axis), and
the checkpoint-reference repair (replace fatal lanes from a known-good
copy).  EDEN's observation — approximate-memory error handling must follow
the physical partition of the resident data — means every one of those
decisions also depends on *placement*: a sharded state must be repaired
shard-locally (no gather) with its counters reduced globally.

`RepairPlan` centralizes both decisions:

  scope       what one pass covers —
                "none"       no-op (repair mode "off" / non-memory modes)
                "tree"       every approximate-region float leaf
                "pages"      rows ``page_ids`` of the leading page axis
                "reference"  fatal lanes replaced from a reference tree
                "inject"     the simulation boundary (bit-flip window)
  placement   where it runs —
                "local"      single-device (or fully replicated) buffers
                "sharded"    ≥1 leaf carries a multi-device NamedSharding;
                             the executable repairs each shard in place
                             under GSPMD and reduces counters globally
                "kernel"     tree- and pages-scope scrubs lower through the
                             Pallas kernels (``kernels/scrub.py`` per leaf;
                             ``scrub_sharded`` for multi-device tree
                             leaves; pages scope is local-placement only —
                             the page gather has no shard_map entry) — the
                             in-place HBM path on real TPUs.  Selected
                             when the backend is TPU (or
                             ``REPRO_KERNEL_PLANS=1`` forces it,
                             interpret-mode on CPU) AND every firing
                             rule's fill maps bit-identically onto a
                             kernel fill (``kernels.common.kernel_fill``)
                             with an encodable detector (pages scope also
                             needs ndim ≥ 2 per repaired leaf for the
                             padding-duplicate count mask); anything else
                             keeps the jnp lowering — never a silent
                             numeric drift.  Lane counters are
                             bit-identical to the jnp path (events stay
                             pass-level, computed from the lane totals).

and owns the compiled executable for the pair.  Plans are cached on the
space by ``(scope, treedef, avals, shardings)`` — one *trace* per state
layout (``ApproxSpace.n_traces`` counts them; asserted in tests), then the
cached executable runs in place with donated buffers.  Stat outputs are
*deltas* (merged host-side), so re-entering with a differently-placed stats
stream can never force a retrace.

Page scrubs bucket their id count to the next power of two: padding entries
duplicate real ids — duplicates scatter identical repaired rows (determin-
istic) and are masked out of the lane counts — so the executable count
stays logarithmic in the pool size instead of linear in faulted pages.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import regions as regions_lib
from ..core import stats as stats_lib
from . import space as space_lib

__all__ = [
    "RepairPlan", "plan_for", "serving_scope", "kernel_plans_enabled",
    "SCOPES",
]

SCOPES = ("none", "tree", "pages", "reference", "inject")

# serving repair-mode knob (ServingConfig.repair) -> plan scope: the ONE
# place the whole-cache-vs-faulted-pages decision lives (the serving
# PageRepairManager routes through this; acceptance — no repair-decision
# logic outside runtime/).
_SERVING_SCOPE = {"off": "none", "whole": "tree", "page": "pages"}


def serving_scope(repair_mode: str) -> str:
    """Map the serving repair mode ("off" | "whole" | "page") to the plan
    scope that implements it."""
    try:
        return _SERVING_SCOPE[repair_mode]
    except KeyError:
        raise ValueError(f"bad serving repair mode {repair_mode!r}") from None


def _sharding_of(leaf) -> Any:
    return getattr(leaf, "sharding", None)


def _placement(shardings: Tuple[Any, ...]) -> str:
    for s in shardings:
        if s is not None and getattr(s, "num_devices", 1) > 1:
            return "sharded"
    return "local"


def kernel_plans_enabled() -> bool:
    """Should tree-scope scrub plans lower through the Pallas kernels?

    ``REPRO_KERNEL_PLANS=1`` forces it (CPU tests run the kernels in
    interpret mode), ``=0`` forces it off; otherwise the kernels engage
    exactly where they are native — a real TPU backend, where the scrub is
    an in-place HBM pass instead of an XLA-fused copy."""
    env = os.environ.get("REPRO_KERNEL_PLANS", "").strip().lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    return jax.default_backend() == "tpu"


def _kernel_eligible(leaves, regions, rule_tree, trigger, scope="tree") -> bool:
    """Every leaf this pass repairs must map onto the kernel path with
    bit-identical semantics: a ``kernel_fill``-representable fill and a
    detector that encodes into the int32[8] scalar operand.  Zero-size
    leaves pass through (nothing to repair) and do not disqualify.
    Pages-scope passes additionally need ndim ≥ 2 on every repaired leaf:
    the kernel's padding-duplicate count mask is a folded-2D *row* bound
    (``scrub_pages`` ``n_valid``), which a 1-D page axis cannot express."""
    from ..kernels import common as kernels_common

    for leaf, region, rule in zip(
        leaves, jax.tree.leaves(regions), jax.tree.leaves(rule_tree)
    ):
        if not space_lib._is_approx_float(leaf, region):
            continue
        if not rule.fires(trigger) or not getattr(leaf, "size", 0):
            continue
        if kernels_common.kernel_fill(rule.fill) is None:
            return False
        if scope == "pages" and getattr(leaf, "ndim", 0) < 2:
            return False
        try:
            rule.detect.constants(leaf.dtype)
        except (TypeError, ValueError):
            return False
    return True


# program name of each executable kind (``jit_<name>`` in a profile)
_PROGRAM_NAMES = {
    "tree": "repair_tree",
    "pages": "repair_pages",
    "reference": "repair_reference",
    "inject": "inject",
}


def _bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n, clamped to the page-axis size."""
    b = 1
    while b < n:
        b <<= 1
    return max(1, min(b, cap))


@dataclasses.dataclass
class RepairPlan:
    """One planned repair pass: scope + placement + compiled executables.

    Obtained via ``ApproxSpace.plan_for`` (cached); ``run`` executes it over
    a concrete tree and returns ``(tree', delta)`` where ``delta`` is a
    functional stats delta (``inject`` scope returns ``(tree', n_flips)``).

    The plan compiles a *per-leaf rule assignment* (README §RepairRule):
    each leaf's Detector × Fill come from the space's ``RuleSet``, the
    plan's ``trigger`` tag gates which rules fire, and the executable
    returns per-rule [nan, inf, events] deltas that ``run`` folds into the
    space's rule ledger.  The rule-set digest joins the cache key, so one
    executable exists per (layout, rule-set).
    """

    space: Any                       # owning ApproxSpace
    scope: str                       # one of SCOPES
    placement: str                   # "local" | "sharded" | "kernel"
    treedef: Any
    regions: Any
    rule_tree: Any                   # per-leaf RepairRule assignment
    index_tree: Any                  # per-leaf rule index (counter ledger)
    n_rules: int
    trigger: str                     # pass tag for rule gating
    bytes_per_run: int               # approx bytes one full-scope pass touches
    page_row_bytes: int              # approx bytes of one page row (pages scope)
    page_capacity: int               # leading page-axis size (pages scope)
    ber: Optional[float] = None      # inject scope only (static per plan)
    shardings: Tuple[Any, ...] = ()  # per-leaf shardings (kernel placement)
    _execs: Dict[Any, Callable] = dataclasses.field(default_factory=dict)

    # ------------------------------------------------------------------- run
    def run(
        self,
        tree: Any,
        *,
        page_ids: Optional[np.ndarray] = None,
        reference: Any = None,
        key: Optional[jax.Array] = None,
        donate: bool = False,
    ) -> Tuple[Any, Any]:
        if self.scope == "none":
            zero = (
                jnp.zeros((), jnp.int32)
                if self.ber is not None
                else stats_lib.zeros()
            )
            return tree, zero
        leaves = tuple(jax.tree_util.tree_flatten(tree)[0])
        rule_counts = None
        if self.scope == "tree":
            out, delta, rule_counts = self._exec(("tree", donate))(leaves)
        elif self.scope == "pages":
            ids = np.asarray(page_ids, np.int32).reshape(-1)
            if ids.size == 0:
                return tree, stats_lib.zeros()
            # duplicates in ids are legal (idempotent), so the clamp floor is
            # the id count itself, not just the page-axis size
            bucket = _bucket(ids.size, max(self.page_capacity, ids.size))
            padded = np.full((bucket,), ids[0], np.int32)
            padded[: ids.size] = ids
            out, delta, rule_counts = self._exec(("pages", bucket, donate))(
                leaves,
                jnp.asarray(padded),
                jnp.asarray(ids.size, jnp.int32),
            )
        elif self.scope == "reference":
            refs = tuple(jax.tree_util.tree_flatten(reference)[0])
            out, delta, rule_counts = self._exec(("reference", donate))(
                leaves, refs
            )
        elif self.scope == "inject":
            out, delta = self._exec(("inject", donate))(leaves, key)
        else:  # pragma: no cover
            raise ValueError(f"bad plan scope {self.scope!r}")
        if rule_counts is not None:
            self.space.record_rule_counts(rule_counts)
        return jax.tree_util.tree_unflatten(self.treedef, out), delta

    # ----------------------------------------------------------- executables
    def _exec(self, variant: Tuple) -> Callable:
        fn = self._execs.get(variant)
        if fn is None:
            fn = self._build(variant)
            self._execs[variant] = fn
        return fn

    def _build(self, variant: Tuple) -> Callable:
        space, cfg, treedef, regions = (
            self.space, self.space.config, self.treedef, self.regions,
        )
        rule_tree, index_tree, n_rules, trigger = (
            self.rule_tree, self.index_tree, self.n_rules, self.trigger,
        )
        kind, donate = variant[0], variant[-1]

        def note():
            # trace-time side effect: the executable-cache counter.  Runs
            # once per trace, never per call — asserted in tests.
            space.n_traces += 1

        if kind == "tree" and self.placement == "kernel":
            # the Pallas lowering of the tree scrub: one in-place kernel per
            # firing leaf (scrub_sharded for multi-device leaves), lane
            # counts bit-identical to the jnp path, events pass-level
            region_leaves = jax.tree.leaves(regions)
            rule_leaves = jax.tree.leaves(rule_tree)
            index_leaves = jax.tree.leaves(index_tree)
            shardings = self.shardings
            from ..kernels import common as kernels_common
            from ..kernels.scrub import scrub as kernel_scrub
            from ..kernels.scrub import scrub_sharded as kernel_scrub_sharded

            def fn(leaves):
                note()
                nan_tot = jnp.zeros((), jnp.int32)
                inf_tot = jnp.zeros((), jnp.int32)
                rc = jnp.zeros((n_rules, 2), jnp.int32)
                out = []
                for leaf, region, rule, idx, sh in zip(
                    leaves, region_leaves, rule_leaves, index_leaves,
                    shardings,
                ):
                    if (
                        not space_lib._is_approx_float(leaf, region)
                        or not rule.fires(trigger)
                        or not leaf.size
                    ):
                        out.append(leaf)
                        continue
                    policy, constant = kernels_common.kernel_fill(rule.fill)
                    if sh is not None and getattr(sh, "num_devices", 1) > 1:
                        fixed, counts = kernel_scrub_sharded(
                            leaf, sh.mesh, sh.spec,
                            policy=policy, constant=constant,
                            detector=rule.detect,
                        )
                    else:
                        fixed, counts = kernel_scrub(
                            leaf, policy=policy, constant=constant,
                            detector=rule.detect,
                        )
                    nan_tot = nan_tot + counts[0]
                    inf_tot = inf_tot + counts[1]
                    rc = rc.at[idx, 0].add(counts[0]).at[idx, 1].add(counts[1])
                    out.append(fixed)
                delta = stats_lib.record_repair(
                    stats_lib.zeros(), nan_tot, inf_tot
                )
                return tuple(out), delta, space_lib._finish_rule_counts(rc)

        elif kind == "tree":

            def fn(leaves):
                note()
                tree = jax.tree_util.tree_unflatten(treedef, leaves)
                out, delta, rc = space_lib.scrub_tree_rules(
                    tree, cfg, stats_lib.zeros(), regions,
                    rule_tree, index_tree, n_rules, trigger,
                )
                return tuple(jax.tree_util.tree_flatten(out)[0]), delta, rc

        elif kind == "pages" and self.placement == "kernel":
            # the Pallas lowering of the page scrub: gather→kernel→scatter
            # per firing leaf (kernels/scrub.scrub_pages), the bucketed id
            # vector's padding duplicates masked out of the lane counts by
            # the kernel's n_valid row bound — counts bit-identical to the
            # jnp path, events pass-level
            region_leaves = jax.tree.leaves(regions)
            rule_leaves = jax.tree.leaves(rule_tree)
            index_leaves = jax.tree.leaves(index_tree)
            from ..kernels import common as kernels_common
            from ..kernels.scrub import scrub_pages as kernel_scrub_pages

            def fn(leaves, page_ids, n_valid):
                note()
                nan_tot = jnp.zeros((), jnp.int32)
                inf_tot = jnp.zeros((), jnp.int32)
                rc = jnp.zeros((n_rules, 2), jnp.int32)
                out = []
                for leaf, region, rule, idx in zip(
                    leaves, region_leaves, rule_leaves, index_leaves
                ):
                    if (
                        not space_lib._is_approx_float(leaf, region)
                        or not rule.fires(trigger)
                        or not leaf.size
                    ):
                        out.append(leaf)
                        continue
                    policy, constant = kernels_common.kernel_fill(rule.fill)
                    fixed, counts = kernel_scrub_pages(
                        leaf, page_ids, policy=policy, constant=constant,
                        detector=rule.detect, n_valid=n_valid,
                    )
                    nan_tot = nan_tot + counts[0]
                    inf_tot = inf_tot + counts[1]
                    rc = rc.at[idx, 0].add(counts[0]).at[idx, 1].add(counts[1])
                    out.append(fixed)
                delta = stats_lib.record_repair(
                    stats_lib.zeros(), nan_tot, inf_tot
                )
                return tuple(out), delta, space_lib._finish_rule_counts(rc)

        elif kind == "pages":

            def fn(leaves, page_ids, n_valid):
                note()
                tree = jax.tree_util.tree_unflatten(treedef, leaves)
                out, delta, rc = space_lib.scrub_pages_tree_rules(
                    tree, page_ids, cfg, stats_lib.zeros(), regions,
                    rule_tree, index_tree, n_rules, trigger,
                    n_valid=n_valid,
                )
                return tuple(jax.tree_util.tree_flatten(out)[0]), delta, rc

        elif kind == "reference":

            def fn(leaves, refs):
                note()
                tree = jax.tree_util.tree_unflatten(treedef, leaves)
                ref = jax.tree_util.tree_unflatten(treedef, refs)
                out, delta, rc = space_lib.reference_scrub_tree_rules(
                    tree, ref, stats_lib.zeros(), regions,
                    rule_tree, index_tree, n_rules,
                )
                return tuple(jax.tree_util.tree_flatten(out)[0]), delta, rc

        elif kind == "inject":
            ber = self.ber

            def fn(leaves, key):
                note()
                tree = jax.tree_util.tree_unflatten(treedef, leaves)
                out, flips = space_lib.inject_tree(tree, key, ber, regions)
                return tuple(jax.tree_util.tree_flatten(out)[0]), flips

        else:  # pragma: no cover
            raise ValueError(f"bad executable kind {kind!r}")

        # a stable program name per kind: ``jit_repair_pages`` etc. in a
        # profile, where every closure would otherwise be ``jit_fn``
        fn.__name__ = fn.__qualname__ = _PROGRAM_NAMES[kind]
        return jax.jit(fn, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# The planner.
# ---------------------------------------------------------------------------


def plan_for(
    space: Any,
    tree: Any,
    *,
    scope: str = "tree",
    ber: Optional[float] = None,
    trigger: str = "forced",
    regions: Any = None,
) -> RepairPlan:
    """Plan one repair pass over ``tree`` for ``space``.

    Scope resolution: "tree" and "pages" are memory-mode mechanisms — in any
    other repair mode they resolve to the "none" no-op plan (matching the
    eager tree functions' mode gate).  "reference" always runs (an explicit
    reference repair is a request, not a schedule), and "inject" always runs
    (the simulation boundary is mode-independent).  Placement is derived
    from the leaves' shardings: any multi-device NamedSharding makes the
    plan shard-local.

    ``trigger`` tags the pass for rule gating (README §RepairRule): only
    rules whose trigger fires on this tag repair their leaves, so one
    (layout, trigger) pair is one executable.  The rule-set digest joins the
    cache key; reference/inject scopes ignore the trigger (forced /
    mode-independent respectively).

    ``regions`` overrides the space's cached region tree (same treedef) —
    the autopilot campaign's per-group injection masks.  The override's
    leaf values join the cache key, so each distinct mask compiles its own
    executable and masks never alias each other's plans.
    """
    if scope not in SCOPES:
        raise ValueError(f"bad plan scope {scope!r}; expected one of {SCOPES}")
    if scope in ("tree", "pages") and space.config.mode != "memory":
        scope = "none"
    if scope not in ("tree", "pages"):
        trigger = "forced"

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    # non-array leaves (plain python scalars in user trees) key by type and
    # pass through the executable untouched, as they did on the eager path
    avals = tuple(
        (
            tuple(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", type(leaf).__name__)),
        )
        for leaf in leaves
    )
    shardings = tuple(_sharding_of(leaf) for leaf in leaves)
    extra = float(ber) if scope == "inject" else None
    kernels_on = kernel_plans_enabled()
    regions_key = (
        None if regions is None else tuple(jax.tree.leaves(regions))
    )
    key = (
        scope, trigger, treedef, avals, shardings, extra,
        space._rules_digest, kernels_on, regions_key,
    )

    plan = space._plan_cache.get(key)
    if plan is not None:
        return plan

    if regions is None:
        regions = space.regions_for(tree)
    rule_tree, index_tree = space.rules_for(tree)
    placement = _placement(shardings)
    if (
        scope == "tree"
        and kernels_on
        and _kernel_eligible(leaves, regions, rule_tree, trigger)
    ):
        placement = "kernel"
    elif (
        scope == "pages"
        and kernels_on
        and placement == "local"   # no shard_map entry for the page gather
        and _kernel_eligible(leaves, regions, rule_tree, trigger, scope)
    ):
        placement = "kernel"
    region_leaves = jax.tree.leaves(regions)
    rule_leaves = jax.tree.leaves(rule_tree)
    approx_bytes = 0
    page_row_bytes = 0
    page_capacity = 0
    for leaf, region, rule in zip(leaves, region_leaves, rule_leaves):
        if not space_lib._is_approx_float(leaf, region):
            continue
        if scope in ("tree", "pages") and not rule.fires(trigger):
            continue    # the ledger counts only what this pass repairs
        nbytes = leaf.size * leaf.dtype.itemsize
        approx_bytes += nbytes
        if leaf.ndim >= 1 and leaf.shape[0]:
            page_row_bytes += nbytes // leaf.shape[0]
            page_capacity = (
                leaf.shape[0] if page_capacity == 0
                else min(page_capacity, leaf.shape[0])
            )

    plan = RepairPlan(
        space=space,
        scope=scope,
        placement=placement,
        treedef=treedef,
        regions=regions,
        rule_tree=rule_tree,
        index_tree=index_tree,
        n_rules=space.ruleset.n_rules,
        trigger=trigger,
        bytes_per_run=0 if scope == "none" else approx_bytes,
        page_row_bytes=page_row_bytes,
        page_capacity=max(page_capacity, 1),
        ber=extra,
        shardings=shardings,
    )
    space._plan_cache[key] = plan
    return plan
