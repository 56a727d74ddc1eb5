"""Serving engine: paged pool roundtrip, targeted scrub, engine-vs-generate
parity, mixed workload with eviction, and page-granular vs whole-cache
repair accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_transformer
from repro.core import stats as stats_lib
from repro.kernels import ops as kernel_ops
from repro.launch.serve import generate
from repro.runtime import ApproxConfig, ApproxSpace
from repro.serving import (
    Engine,
    PagedKVPool,
    PageRepairManager,
    ServingConfig,
)


@pytest.fixture(scope="module")
def model_params():
    return tiny_transformer()


def _mixed_engine(model, params, *, repair, ber, max_new=6):
    """8 requests of up to 5 pages over a 10-page pool: admission control
    and preemption are live (worst-case demand ~3x capacity)."""
    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=10, max_batch=4, max_pages_per_request=5,
        repair=repair, ber=ber, sweep_interval=8, sweep_pages=2, seed=3,
    ))
    for i in range(8):
        prompt = jax.random.randint(jax.random.PRNGKey(i), (5 + i % 3,), 1, 96)
        eng.add_request(prompt, max_new=max_new)
    return eng


# -------------------------------------------------------------------- pool
def test_pool_alloc_free_and_gather_scatter_roundtrip(model_params):
    model, _ = model_params
    cfg = ServingConfig(page_size=4, n_pages=6, max_batch=2,
                        max_pages_per_request=3)
    pool = PagedKVPool(model, ApproxSpace(mode="memory"), cfg)

    pages = pool.alloc(2)
    assert pages is not None and pool.n_free == 4
    assert pool.alloc(5) is None            # admission-control signal

    bt = pool.block_table(pages)[None, :]   # (1, 3), null-padded
    assert bt[0, 2] == pool.null_page
    view = pool.gather(bt)
    k = jax.tree.leaves(view)[0]            # (L, 1, 12, K, Dh)
    assert k.shape[2] == cfg.max_pages_per_request * cfg.page_size

    stamped = jax.tree.map(lambda v: v + 7.0, view)
    pool.scatter(stamped, bt)
    back = pool.gather(bt)
    for a, b in zip(jax.tree.leaves(stamped), jax.tree.leaves(back)):
        # allocated pages roundtrip exactly; null-page positions may differ
        # (duplicate scatter writes collide there by design)
        np.testing.assert_array_equal(
            np.asarray(a[:, :, :8]), np.asarray(b[:, :, :8])
        )

    pool.free(pages)
    assert pool.n_free == 6


def test_pool_alloc_zeroes_recycled_pages(model_params):
    model, _ = model_params
    cfg = ServingConfig(page_size=4, n_pages=4, max_batch=1,
                        max_pages_per_request=2)
    pool = PagedKVPool(model, ApproxSpace(mode="memory"), cfg)
    pages = pool.alloc(2)
    pool.tree = jax.tree.map(lambda l: l + jnp.nan, pool.tree)  # poison all
    pool.free(pages)
    again = pool.alloc(2)                  # recycled: must come back clean
    idx = jnp.asarray(again, jnp.int32)
    for leaf in jax.tree.leaves(pool.tree):
        assert bool(jnp.isfinite(leaf[idx]).all())


# ---------------------------------------------------------- targeted scrub
def test_space_scrub_pages_repairs_only_named_pages():
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero"))
    tree = {"k": jnp.zeros((4, 8)).at[1, 0].set(jnp.nan).at[3, 2].set(jnp.nan)}
    out, stats = space.scrub_pages(tree, jnp.asarray([1]), stats_lib.zeros())
    assert bool(jnp.isfinite(out["k"][1]).all())
    assert bool(jnp.isnan(out["k"][3, 2]))          # untouched page keeps NaN
    assert int(stats["nan_found"]) == 1
    assert int(stats["events"]) == 1
    # no-op outside memory mode
    off = ApproxSpace(ApproxConfig(mode="off"))
    same, _ = off.scrub_pages(tree, jnp.asarray([1, 3]), stats_lib.zeros())
    assert bool(jnp.isnan(same["k"][1, 0]))


def test_kernel_scrub_pages_page_view():
    x = jnp.ones((6, 64), jnp.float32).at[2, 5].set(jnp.nan).at[4, 9].set(jnp.nan)
    fixed, counts = kernel_ops.scrub_pages(x, jnp.asarray([2]), policy="zero")
    assert bool(jnp.isfinite(fixed[2]).all())
    assert bool(jnp.isnan(fixed[4, 9]))             # outside the page view
    assert int(counts[0]) == 1                      # nan lanes in the view


# ----------------------------------------------------------------- parity
def test_engine_matches_generate_at_zero_ber(model_params):
    model, params = model_params
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 1, 96)
    ref, _ = generate(model, params, prompt, max_new=5, max_seq=16)

    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=8, max_batch=2, max_pages_per_request=4,
    ))
    rids = [eng.add_request(prompt[b], max_new=5) for b in range(2)]
    results = eng.run()
    got = np.asarray([results[r]["tokens"] for r in rids])
    np.testing.assert_array_equal(np.asarray(ref), got)


# ---------------------------------------------------------- mixed workload
def test_mixed_workload_evicts_and_completes(model_params):
    model, params = model_params
    eng = _mixed_engine(model, params, repair="page", ber=0.0)
    results = eng.run()
    assert len(results) == 8
    assert all(len(r["generated"]) == 6 for r in results.values())
    assert eng.sched.n_preemptions > 0              # page pressure was real
    assert any(r["n_preempted"] > 0 for r in results.values())
    assert eng.pool.n_free == 10                    # no page leaks


def test_page_repair_scrubs_fewer_bytes_than_whole(model_params):
    model, params = model_params
    whole = _mixed_engine(model, params, repair="whole", ber=1e-3, max_new=5)
    whole.run()
    page = _mixed_engine(model, params, repair="page", ber=1e-3, max_new=5)
    page.run()

    # same seed + same schedule => identical fault exposure; both must have
    # actually repaired something for the comparison to mean anything
    assert whole.stats_dict()["events"] > 0
    assert page.stats_dict()["events"] > 0
    assert 0 < page.pool.scrubbed_bytes < whole.pool.scrubbed_bytes
    mw, mp = whole.metrics(), page.metrics()
    assert (
        mp["scrubbed_bytes_per_token"] < mw["scrubbed_bytes_per_token"]
    )


# ------------------------------------------------------- kernel routing
def test_kernel_counters_route_to_touched_pages(model_params):
    model, _ = model_params
    cfg = ServingConfig(page_size=4, n_pages=4, max_batch=1,
                        max_pages_per_request=2, repair="page")
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero"))
    pool = PagedKVPool(model, space, cfg)
    mgr = PageRepairManager(pool, space, cfg)

    # poison an allocated page that no step will touch (cold): reactive
    # detection over touched pages alone would never find it.  (It must be
    # allocated — routing skips freed pages, whose faults belong to no one.)
    pages = pool.alloc(3)
    cold = pages[-1]
    pool.tree = jax.tree.map(
        lambda l: l.at[cold, 0, 0, 0, 0].set(jnp.nan), pool.tree
    )
    counts = jnp.zeros((8,), jnp.int32).at[kernel_ops.MM_EV_TOTAL].set(3)
    mgr.note_kernel(counts, touched=[cold])

    assert space.stats_dict()["events"] == 3        # unified stream
    assert pool.page_events[cold] == 3              # per-page ledger
    stats = mgr.repair_step(touched=[], stats=stats_lib.zeros())
    assert int(stats["nan_found"]) == 2             # both pool leaves (k, v)
    for leaf in jax.tree.leaves(pool.tree):
        assert bool(jnp.isfinite(leaf[cold]).all())
    assert pool.scrubbed_bytes > 0

    # a freed page reported through the same route is never charged: its
    # faults belong to no live request
    free_probe = 3
    assert pool.is_free(free_probe)
    mgr.note_kernel(counts, touched=[free_probe])
    assert pool.page_events[free_probe] == 0


# ------------------------------------------------------------------ config
def test_serving_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(repair="bogus")
    with pytest.raises(ValueError):
        ServingConfig(n_pages=2, max_pages_per_request=4)
    cfg = ServingConfig(page_size=4, max_pages_per_request=3)
    assert cfg.max_seq == 12
    assert cfg.pages_for(9) == 3


@pytest.mark.parametrize("paged_decode", ["auto", "off"])
def test_range_guard_keeps_readouts_finite(model_params, paged_decode):
    """Exponent flips that leave huge finite K/V lanes pass NaN/Inf
    detection and overflow into non-finite logits; the engine counts those
    readout rows on the fused and the gathered path alike, and its default
    space's range guard keeps them at zero."""
    from repro.serving import engine_space

    model, params = model_params
    spaces = {
        "nan_inf": lambda: ApproxSpace(
            engine_space(model).config, max_magnitude=None
        ),
        "default": lambda: engine_space(model),
    }
    rows = {}
    for name, make_space in spaces.items():
        eng = Engine(model, params, ServingConfig(
            page_size=4, n_pages=16, max_batch=4, max_pages_per_request=4,
            paged_decode=paged_decode, ber=3e-3, seed=1,
        ), space=make_space())
        assert (eng._paged_fn is not None) == (paged_decode == "auto")
        for i in range(4):
            prompt = jax.random.randint(jax.random.PRNGKey(i), (6,), 1, 96)
            eng.add_request(prompt, max_new=8)
        eng.run()
        assert eng.stats_dict()["flips"] > 0
        rows[name] = eng.metrics()["nonfinite_logit_rows"]
    assert rows["nan_inf"] > 0
    assert rows["default"] == 0


# ---------------------------------------------------------- program names
def test_repair_and_reset_programs_have_stable_names(model_params):
    """A profile names each program by its jitted function: the repair
    plan's executables and the pool's page reset carry their own names, so
    trace readers can tell the scrub from the injection and the reset."""
    from repro.serving.engine import engine_space
    from repro.serving.pool import pool_reset_pages

    model, _ = model_params
    cfg = ServingConfig(page_size=4, n_pages=4, max_batch=1,
                        max_pages_per_request=2, repair="page")
    space = engine_space(model)
    pool = PagedKVPool(model, space, cfg)
    leaves = tuple(jax.tree.leaves(pool.tree))
    ids = jnp.zeros((2,), jnp.int32)

    def module(fn, *args):
        return fn.lower(*args).as_text().split("module @", 1)[1].split()[0]

    pages = space.plan_for(pool.tree, scope="pages", trigger="reactive")
    tree = space.plan_for(pool.tree, scope="tree", trigger="reactive")
    inject = space.plan_for(pool.tree, scope="inject", ber=1e-3)
    ref = space.plan_for(pool.tree, scope="reference")
    assert module(pages._exec(("pages", 2, False)), leaves, ids,
                  jnp.asarray(1, jnp.int32)) == "jit_repair_pages"
    assert module(tree._exec(("tree", False)), leaves) == "jit_repair_tree"
    assert module(inject._exec(("inject", True)), leaves,
                  jax.random.PRNGKey(0)) == "jit_inject"
    assert module(ref._exec(("reference", False)), leaves,
                  leaves) == "jit_repair_reference"
    assert module(pool_reset_pages, pool.tree, ids) == "jit_pool_reset_pages"
