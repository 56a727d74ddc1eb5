"""Multidev lane (scripts/ci.sh multidev): the mesh-native repair pipeline
under 8 fake host devices.

These tests verify the PR-3 acceptance contract on a real multi-device
topology: sharded compiled scrub/inject bit-identical to the eager
single-device path with identical GLOBAL counters (reduced once, never
per-replica), one executable trace per (treedef, avals, shardings), page
scrubs on a page-axis-sharded pool, the shard_map Pallas scrub, train_loop
on a mesh, and the elastic reshard + post-restore reference repair.

Collected (and skipped) in the tier-1 single-device run; executed by
``scripts/ci.sh multidev`` in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 REPRO_MULTIDEV=1``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import stats as stats_lib
from repro.launch.mesh import make_mesh
from repro.runtime import ApproxConfig, ApproxSpace
from repro.runtime.space import inject_tree, scrub_tree

pytestmark = [
    pytest.mark.multidev,
    pytest.mark.skipif(
        jax.device_count() < 8,
        reason="needs the 8-device lane (scripts/ci.sh multidev)",
    ),
]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((4, 2), ("data", "model"))


def poisoned_tree(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (32, 16), jnp.float32)
    mu = jax.random.normal(k2, (16, 8), jnp.float32)
    w = w.at[3, 4].set(jnp.nan).at[17, 2].set(jnp.inf)
    mu = mu.at[0, 0].set(jnp.nan)
    return {"w": w, "mu": mu, "step": jnp.zeros((), jnp.int32)}


def shard(tree, mesh):
    return jax.device_put(tree, {
        "w": NamedSharding(mesh, P("data", "model")),
        "mu": NamedSharding(mesh, P("data", None)),
        "step": NamedSharding(mesh, P()),
    })


# ----------------------------------------------------------------- parity
def test_sharded_scrub_bitwise_parity_and_global_counts(mesh):
    """Compiled scrub over FSDP/TP-sharded state == eager single-device
    scrub, bit for bit, with identical global counters (zero policy: the
    repair is elementwise, so sharding cannot perturb it)."""
    tree = poisoned_tree()
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero"), mesh=mesh)
    eager, eager_stats = scrub_tree(
        tree, space.config, stats_lib.zeros(), space.regions_for(tree)
    )
    out, out_stats = space.scrub(shard(tree, mesh), stats_lib.zeros())
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats_lib.as_dict(eager_stats) == stats_lib.as_dict(out_stats)
    assert stats_lib.as_dict(out_stats)["nan_found"] == 2
    assert stats_lib.as_dict(out_stats)["inf_found"] == 1
    # counted once globally: events is 1 scrub pass, not 8 replicas' worth
    assert stats_lib.as_dict(out_stats)["events"] == 1
    assert space.plan_for(shard(tree, mesh)).placement == "sharded"


def test_sharded_neighbor_mean_bitwise_parity(mesh):
    """neighbor_mean is now tile-local with an order-fixed pairwise
    reduction (ROADMAP leftover): the fill value no longer depends on the
    reduction order GSPMD picks, so the sharded compiled scrub is
    BIT-IDENTICAL to the eager single-device path — not merely allclose —
    and the integer counters stay exactly equal."""
    tree = poisoned_tree(1)
    space = ApproxSpace(
        ApproxConfig(mode="memory", policy="neighbor_mean"), mesh=mesh
    )
    eager, eager_stats = scrub_tree(
        tree, space.config, stats_lib.zeros(), space.regions_for(tree)
    )
    out, out_stats = space.scrub(shard(tree, mesh), stats_lib.zeros())
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(out)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
        else:
            np.testing.assert_array_equal(a, b)
    assert stats_lib.as_dict(eager_stats) == stats_lib.as_dict(out_stats)


def test_sharded_inject_bitwise_parity_and_global_flips(mesh):
    """Same key + BER => bit-identical flips through the sharded compiled
    executable and the eager host path, with the ground-truth flip count
    reduced globally (not once per replica)."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(5), (128, 128))}
    key = jax.random.PRNGKey(6)
    space = ApproxSpace(ApproxConfig(ber=1e-5), mesh=mesh)
    stree = jax.device_put(
        tree, {"w": NamedSharding(mesh, P("data", "model"))}
    )

    eager, eager_flips = inject_tree(
        tree, key, 1e-5, space.regions_for(tree)
    )
    out, flips = space.inject(stree, key, 1e-5)
    np.testing.assert_array_equal(
        np.asarray(eager["w"]), np.asarray(out["w"])
    )
    assert int(eager_flips) == int(flips) > 0
    assert space.stats_dict()["flips"] == int(flips)


# ------------------------------------------------------------------ caching
def test_one_trace_per_layout(mesh):
    """One executable trace per (treedef, avals, shardings): repeated calls
    reuse the cache; a new sharding layout (same treedef/avals) compiles a
    second executable."""
    tree = poisoned_tree(2)
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero"), mesh=mesh)
    stree = shard(tree, mesh)
    out, _ = space.scrub(stree, stats_lib.zeros())
    assert space.n_traces == 1
    for _ in range(3):
        out, _ = space.scrub(out, stats_lib.zeros())
    assert space.n_traces == 1, "same layout must never retrace"

    replicated = jax.device_put(
        tree, jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    )
    space.scrub(replicated, stats_lib.zeros())
    assert space.n_traces == 2, "a new sharding layout is a new executable"


# ----------------------------------------------------------- serving pool
def test_pool_page_axis_sharding_and_page_scrub_parity(mesh):
    """The engine's pool registers page-axis shardings from the space's
    mesh; page scrubs over the sharded pool are bit-identical (zero policy)
    to the same scrub on an unsharded copy, with identical counters."""
    from repro.serving import Engine, ServingConfig

    from conftest import tiny_transformer

    model, params = tiny_transformer()
    cfg = ServingConfig(
        page_size=4, n_pages=7, max_batch=2, max_pages_per_request=4, seed=0
    )
    sp = ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None),
        mesh=mesh,
    )
    eng = Engine(model, params, cfg, space=sp)
    assert eng.pool.shardings is not None
    specs = {str(s.spec) for s in jax.tree.leaves(eng.pool.shardings)}
    # n_pages+1 = 8 divides the data axis (4): the page axis IS sharded
    assert any("data" in s for s in specs), specs

    # poison two pages; scrub them on both the sharded pool and a host copy
    host = jax.device_get(eng.pool.tree)
    poison = jax.tree.map(
        lambda v: jnp.asarray(v).at[2, 0, 0, 0, 0].set(jnp.nan)
        .at[5, 0, 1, 0, 0].set(jnp.inf),
        host,
    )
    eng.pool.tree = jax.device_put(poison, eng.pool.shardings)
    unsharded = ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None)
    )
    ref_fixed, ref_stats = unsharded.scrub_pages(
        poison, [2, 5], stats_lib.zeros()
    )
    stats = eng.pool.scrub_pages([2, 5], stats_lib.zeros())
    assert stats_lib.as_dict(ref_stats) == stats_lib.as_dict(stats)
    for a, b in zip(
        jax.tree.leaves(ref_fixed), jax.tree.leaves(eng.pool.tree)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and the engine serves end-to-end on the sharded pool
    rid = eng.add_request([5, 6, 7], max_new=4)
    results = eng.run()
    assert len(results[rid]["generated"]) == 4


def test_engine_params_sharded_not_replicated(mesh):
    """serve_shardings threading (ROADMAP leftover): a mesh-carrying engine
    device_puts model params onto their logical-axis shardings — params are
    no longer replicated next to the sharded pool."""
    from repro.serving import Engine, ServingConfig

    from conftest import tiny_transformer

    model, params = tiny_transformer()
    cfg = ServingConfig(
        page_size=4, n_pages=7, max_batch=2, max_pages_per_request=4, seed=0
    )
    sp = ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None),
        mesh=mesh,
    )
    eng = Engine(model, params, cfg, space=sp)
    assert eng.params_shardings is not None
    leaves = jax.tree.leaves(eng.params)
    assert any(
        getattr(leaf.sharding, "num_devices", 1) > 1
        and not leaf.sharding.is_fully_replicated
        for leaf in leaves
    ), "at least one param must be genuinely sharded"
    # tokens still come out right on the sharded params
    rid = eng.add_request([3, 4, 5], max_new=3)
    results = eng.run()
    assert len(results[rid]["generated"]) == 3

    # a mesh-free engine keeps the legacy behavior (no device_put)
    eng2 = Engine(model, params, cfg, space=ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None)
    ))
    assert eng2.params_shardings is None


# ----------------------------------------------------------- kernel entry
def test_scrub_sharded_kernel_shard_local(mesh):
    """The shard_map Pallas scrub repairs each device's local rows with no
    gather; NaN/Inf lane counts are exact global totals (events follow the
    per-shard tiling, like the fused kernels' block shapes)."""
    from repro.kernels.scrub import scrub, scrub_sharded

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    x = x.at[3, 4].set(jnp.nan).at[17, 2].set(jnp.inf)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
    ref, ref_counts = scrub(x, policy="zero")
    out, counts = scrub_sharded(xs, mesh, P("data", "model"), policy="zero")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert int(counts[0]) == int(ref_counts[0]) == 1     # nan lanes
    assert int(counts[1]) == int(ref_counts[1]) == 1     # inf lanes

    # partial sharding: replicas along the unused ("model") axis must NOT
    # multiply the global counts (psum runs only over the spec's axes)
    xp = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    out_p, counts_p = scrub_sharded(xp, mesh, P("data", None), policy="zero")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out_p))
    assert int(counts_p[0]) == 1 and int(counts_p[1]) == 1

    # fully replicated: each device already holds the global array — no
    # reduction at all, counts stay global
    xr = jax.device_put(x, NamedSharding(mesh, P()))
    _, counts_r = scrub_sharded(xr, mesh, P(), policy="zero")
    assert int(counts_r[0]) == 1 and int(counts_r[1]) == 1


# ------------------------------------------------------------- train loop
def test_train_loop_on_mesh_runs_sharded_repair(mesh):
    """train_loop(mesh=...) threads train_state_shardings into the space:
    the state is sharded, injection windows compile against the placements,
    and the flips counter accumulates ground truth."""
    from conftest import tiny_transformer
    from repro.launch.train import make_optimizer, train_loop

    model, _ = tiny_transformer()
    model = type(model)(dataclasses.replace(model.cfg))
    opt = make_optimizer(total=3)

    def data_fn(i):
        return {
            "tokens": jax.random.randint(jax.random.PRNGKey(i), (8, 16), 1, 96)
        }

    space = ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", ber=1e-5)
    )
    state, history = train_loop(
        model, opt, data_fn, steps=2, key=jax.random.PRNGKey(0),
        ber=1e-5, mesh=mesh, space=space, log_every=1,
    )
    assert space.mesh is mesh
    assert history[-1]["flips"] > 0
    w = jax.tree.leaves(state["params"])[0]
    assert w.sharding.mesh.shape == mesh.shape
    assert np.isfinite(history[-1]["loss"])


# ------------------------------------------------------ elastic reshard
def test_elastic_reshard_restore_and_reference_repair(mesh, tmp_path):
    """Save from one mesh shape, restore onto another: tree equality, the
    new shardings, and a post-restore reference repair that runs on the NEW
    mesh's placements (the checkpoint/manager.py contract, now tested)."""
    from repro.checkpoint.manager import CheckpointManager

    mesh_a = mesh                                     # (data=4, model=2)
    mesh_b = make_mesh((2, 4), ("data", "model"))  # restored topology

    tree = poisoned_tree(3)
    tree = {  # clean state for the save (scrub-on-save would fix it anyway)
        "w": jnp.nan_to_num(tree["w"], posinf=1.0),
        "mu": jnp.nan_to_num(tree["mu"]),
        "step": tree["step"],
    }
    state_a = shard(tree, mesh_a)
    mgr = CheckpointManager(str(tmp_path), scrub=True)
    mgr.save(7, state_a, blocking=True)

    shardings_b = {
        "w": NamedSharding(mesh_b, P("data", "model")),
        "mu": NamedSharding(mesh_b, P("data", None)),
        "step": NamedSharding(mesh_b, P()),
    }
    like = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree
    )
    restored, step = mgr.restore(like=like, shardings=shardings_b, repair=True)
    assert step == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert restored["w"].sharding.mesh.shape == mesh_b.shape

    # flips strike AFTER the restore; the reference repair heals them on
    # the new mesh's shardings and records the events
    poisoned = dict(restored, w=restored["w"].at[1, 2].set(jnp.nan))
    events0 = mgr.space.stats_dict()["events"]
    healed = mgr.reference_repair(poisoned)
    np.testing.assert_array_equal(
        np.asarray(healed["w"]), np.asarray(tree["w"])
    )
    assert healed["w"].sharding.mesh.shape == mesh_b.shape
    assert mgr.space.stats_dict()["events"] == events0 + 1
    assert mgr.space.stats_dict()["nan_found"] >= 1


# -------------------------------------------------------- paged attention
def test_paged_decode_over_sharded_pool_matches_unsharded(mesh):
    """The fused kernel family attends over a "page"->"data"-sharded pool:
    tokens identical to the unsharded engine, ZERO full-view copies across
    admission, prefill and decode — the page-axis sharding pays off end to
    end (no gather ever rebuilds a contiguous view)."""
    from repro.serving import Engine, ServingConfig

    from conftest import tiny_transformer

    model, params = tiny_transformer()
    cfg = ServingConfig(
        page_size=4, n_pages=7, max_batch=2, max_pages_per_request=4,
        ber=1e-3, seed=11,
    )
    sharded = Engine(model, params, cfg, space=ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None),
        mesh=mesh,
    ))
    assert sharded.pool.shardings is not None
    assert sharded._paged_fn is not None, "fused path must engage on mesh"
    assert sharded._prefill_fn is not None
    plain = Engine(model, params, cfg, space=ApproxSpace(
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None)
    ))
    prompts = [[5, 6, 7], [11, 3]]
    rids_s = [sharded.add_request(p, max_new=5) for p in prompts]
    rids_p = [plain.add_request(p, max_new=5) for p in prompts]
    res_s, res_p = sharded.run(), plain.run()
    for rs, rp in zip(rids_s, rids_p):
        assert res_s[rs]["tokens"] == res_p[rp]["tokens"]
    # prefill AND decode ran straight off the sharded pool
    assert sharded.pool.n_gathers == 0
    assert sharded.pool.n_scatters == 0


def test_splitk_decode_over_sharded_pool_matches_serial(mesh):
    """Split-K flash decoding over the sharded pool: the grid-parallel page
    walk (log-sum-exp merge) emits the same tokens and per-page fault
    ledger as the serial walk on the same mesh."""
    from repro.serving import Engine, ServingConfig

    from conftest import tiny_transformer

    model, params = tiny_transformer()

    def build(split_k):
        eng = Engine(model, params, ServingConfig(
            page_size=4, n_pages=12, max_batch=2, max_pages_per_request=8,
            ber=1e-3, seed=5, split_k=split_k,
        ), space=ApproxSpace(
            ApproxConfig(mode="memory", policy="zero", max_magnitude=None),
            mesh=mesh,
        ))
        prompt = jax.random.randint(jax.random.PRNGKey(9), (26,), 1, 96)
        eng.add_request(prompt, max_new=6)         # context spans 8 pages
        eng.add_request([4, 17, 2], max_new=6)
        return eng

    split = build(0)                               # auto: M=8 -> 4 splits
    assert split._split_k == 4 and split.pool.shardings is not None
    res_s = split.run()
    serial = build(1)
    res_1 = serial.run()
    for rid in res_s:
        assert res_s[rid]["tokens"] == res_1[rid]["tokens"]
    assert split.stats_dict() == serial.stats_dict()
    np.testing.assert_array_equal(
        split.pool.page_events, serial.pool.page_events
    )
    assert split.pool.n_gathers == 0 and split.pool.n_scatters == 0
