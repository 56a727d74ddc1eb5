"""Multidev lane: the device-local sharded serving hot path.

Kernel contract (ISSUE 10 tentpole): `paged_attention_sharded` /
`paged_prefill_sharded` partition the block-table walk by page ownership
(the pool's "page"->"data" sharding rule), so decode, chunked prefill, and
split-K reads never cross device boundaries.  Parity targets under
injected/poisoned flips:

  * integer ledgers (slot_counts, counts) — bit-identical to the SERIAL
    kernel: every block slot is owned by exactly one device;
  * float output — bit-identical to `paged_*_shard_ref`, the single-device
    oracle running the identical ownership partition + per-split device
    merge (the serial kernel groups its accumulation differently, so its
    float output is only allclose);
  * engine end-to-end — same tokens as the single-device engine, zero
    full-view copies, with the shard_map path demonstrably engaged.

Collected (and skipped) in the tier-1 single-device run; executed by
``scripts/ci.sh multidev`` / the ``traffic`` lane with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 REPRO_MULTIDEV=1``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import paged_attention as pk
from repro.launch.mesh import make_mesh
from repro.runtime import ApproxConfig, ApproxSpace

pytestmark = [
    pytest.mark.multidev,
    pytest.mark.skipif(
        jax.device_count() < 8,
        reason="needs the 8-device lane (scripts/ci.sh multidev)",
    ),
]

N_SHARDS = 4          # the mesh's "data" axis


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((N_SHARDS, 2), ("data", "model"))


def _pool(seed=0, P_pages=8, L=1, pg=4, Kh=2, Dh=8):
    """A small page pool with fatal lanes parked in several pages (spread
    across every ownership shard) — the last row doubles as null padding."""
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    kp = jax.random.normal(kk, (P_pages, L, pg, Kh, Dh), jnp.float32)
    vp = jax.random.normal(kv, (P_pages, L, pg, Kh, Dh), jnp.float32)
    kp = kp.at[1, 0, 2, 0, 3].set(jnp.nan).at[6, 0, 0, 1, 0].set(jnp.inf)
    vp = vp.at[3, 0, 1, 1, 5].set(jnp.nan).at[7, 0, 0, 0, 0].set(jnp.nan)
    return kp, vp


def _shard_pool(mesh, kp, vp):
    s = NamedSharding(mesh, P("data", None, None, None, None))
    return jax.device_put(kp, s), jax.device_put(vp, s)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


BT = np.array([[0, 3, 5, 7], [2, 6, 7, 7]], np.int32)    # 7 = null padding
POS = np.array([13, 9], np.int32)


# ---------------------------------------------------------------- decode
def test_sharded_decode_kernel_parity(mesh):
    kp, vp = _pool()
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 8), jnp.float32)
    layer = jnp.int32(0)

    out_ser, slot_ser, cnt_ser = paged = pk.paged_attention_raw(
        q, kp, vp, BT, POS, layer
    )
    out_ref, slot_ref, cnt_ref = pk.paged_attention_shard_ref(
        q, kp, vp, BT, POS, layer, n_shards=N_SHARDS
    )
    ksh, vsh = _shard_pool(mesh, kp, vp)
    out_sh, slot_sh, cnt_sh = pk.paged_attention_sharded(
        q, ksh, vsh, BT, POS, layer, mesh=mesh, axis="data"
    )
    # the poison was detected at all (the test has teeth)
    assert int(cnt_ser[pk.EV_TOTAL]) > 0
    # integer ledgers: bit-identical to the SERIAL kernel
    np.testing.assert_array_equal(np.asarray(slot_sh), np.asarray(slot_ser))
    np.testing.assert_array_equal(np.asarray(cnt_sh), np.asarray(cnt_ser))
    np.testing.assert_array_equal(np.asarray(slot_ref), np.asarray(slot_ser))
    np.testing.assert_array_equal(np.asarray(cnt_ref), np.asarray(cnt_ser))
    # float output: bit-identical to the shard oracle, allclose to serial
    np.testing.assert_array_equal(_bits(out_sh), _bits(out_ref))
    np.testing.assert_allclose(
        np.asarray(out_sh), np.asarray(out_ser), rtol=2e-6, atol=2e-6
    )
    del paged


def test_sharded_decode_composes_with_splitk(mesh):
    """splits > 1 inside the sharded walk: nd x splits partials merge to
    the same bits as the shard oracle at the same splits, same ledgers as
    serial."""
    kp, vp = _pool(seed=3)
    q = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 8), jnp.float32)
    layer = jnp.int32(0)
    _, slot_ser, cnt_ser = pk.paged_attention_splitk_raw(
        q, kp, vp, BT, POS, layer, splits=2
    )
    out_ref, _, _ = pk.paged_attention_shard_ref(
        q, kp, vp, BT, POS, layer, n_shards=N_SHARDS, splits=2
    )
    ksh, vsh = _shard_pool(mesh, kp, vp)
    out_sh, slot_sh, cnt_sh = pk.paged_attention_sharded(
        q, ksh, vsh, BT, POS, layer, mesh=mesh, axis="data", splits=2
    )
    np.testing.assert_array_equal(np.asarray(slot_sh), np.asarray(slot_ser))
    np.testing.assert_array_equal(np.asarray(cnt_sh), np.asarray(cnt_ser))
    np.testing.assert_array_equal(_bits(out_sh), _bits(out_ref))


# --------------------------------------------------------------- prefill
def test_sharded_prefill_kernel_parity(mesh):
    kp, vp = _pool(seed=5)
    C = 4
    q = jax.random.normal(jax.random.PRNGKey(6), (2, C, 4, 8), jnp.float32)
    q_start = np.array([8, 4], np.int32)
    layer = jnp.int32(0)

    out_ser, slot_ser, cnt_ser = pk.paged_prefill_raw(
        q, kp, vp, BT, q_start, layer
    )
    out_ref, slot_ref, cnt_ref = pk.paged_prefill_shard_ref(
        q, kp, vp, BT, q_start, layer, n_shards=N_SHARDS
    )
    ksh, vsh = _shard_pool(mesh, kp, vp)
    out_sh, slot_sh, cnt_sh = pk.paged_prefill_sharded(
        q, ksh, vsh, BT, q_start, layer, mesh=mesh, axis="data"
    )
    assert int(cnt_ser[pk.EV_TOTAL]) > 0
    np.testing.assert_array_equal(np.asarray(slot_sh), np.asarray(slot_ser))
    np.testing.assert_array_equal(np.asarray(cnt_sh), np.asarray(cnt_ser))
    np.testing.assert_array_equal(np.asarray(slot_ref), np.asarray(slot_ser))
    np.testing.assert_array_equal(np.asarray(cnt_ref), np.asarray(cnt_ser))
    np.testing.assert_array_equal(_bits(out_sh), _bits(out_ref))
    np.testing.assert_allclose(
        np.asarray(out_sh), np.asarray(out_ser), rtol=2e-6, atol=2e-6
    )


# --------------------------------------------------------- engine, e2e
def _spaces(mesh):
    mk = lambda m: ApproxSpace(  # noqa: E731
        ApproxConfig(mode="memory", policy="zero", max_magnitude=None),
        mesh=m,
    )
    return mk(mesh), mk(None)


def test_engine_sharded_hot_path_token_parity(mesh):
    """n_pages+1 divides the data axis => the engine resolves the pool's
    page shard axis and runs decode AND chunked prefill under shard_map,
    emitting the same tokens as the single-device engine with zero
    full-view copies."""
    from conftest import tiny_transformer
    from repro.serving import Engine, ServingConfig

    model, params = tiny_transformer()
    cfg = ServingConfig(
        page_size=4, n_pages=7, max_batch=2, max_pages_per_request=4,
        ber=1e-3, seed=23, prefill_chunk=4,
    )
    sp_mesh, sp_plain = _spaces(mesh)
    sharded = Engine(model, params, cfg, space=sp_mesh)
    assert sharded._kernel_shard is not None, (
        "8 pool rows over data=4 must engage the sharded walk"
    )
    assert sharded._kernel_shard[1] == "data"
    plain = Engine(model, params, cfg, space=sp_plain)
    assert plain._kernel_shard is None
    prompts = [[5, 6, 7, 8, 9, 10], [11, 3]]
    rids_s = [sharded.add_request(p, max_new=5) for p in prompts]
    rids_p = [plain.add_request(p, max_new=5) for p in prompts]
    res_s, res_p = sharded.run(), plain.run()
    for rs, rp in zip(rids_s, rids_p):
        assert res_s[rs]["tokens"] == res_p[rp]["tokens"]
    assert sharded.pool.n_gathers == 0
    assert sharded.pool.n_scatters == 0


def test_engine_indivisible_pages_degrade_gracefully(mesh):
    """13 pool rows over data=4: spec_for_leaf degrades to replicated, the
    shard axis resolves to None, and the engine keeps the single-device
    kernel walk (no shard_map) — serving still works."""
    from conftest import tiny_transformer
    from repro.serving import Engine, ServingConfig

    model, params = tiny_transformer()
    cfg = ServingConfig(
        page_size=4, n_pages=12, max_batch=2, max_pages_per_request=4,
        seed=7,
    )
    sp_mesh, _ = _spaces(mesh)
    eng = Engine(model, params, cfg, space=sp_mesh)
    assert eng.pool.page_shard_axis() is None
    assert eng._kernel_shard is None
    rid = eng.add_request([5, 6, 7], max_new=3)
    assert len(eng.run()[rid]["generated"]) == 3


def test_traffic_sharded_token_parity(mesh):
    """CI `traffic` lane assertion: the load harness replayed against a
    sharded engine and a single-device engine yields identical per-request
    token streams, and regenerating the workload from the same seed yields
    identical arrivals."""
    from conftest import tiny_transformer
    from repro.serving import Engine, ServingConfig
    from repro.serving.workload import WorkloadConfig, generate_arrivals

    from benchmarks.traffic import drive

    wl = WorkloadConfig(
        n_requests=6, arrival_rate=0.7, prompt_len=(2, 6),
        long_prompt_len=(8, 10), long_frac=0.3, output_len=(2, 5),
        seed=13,
    )
    arrivals = generate_arrivals(wl)
    assert [
        (a.step, a.prompt, a.max_new) for a in generate_arrivals(wl)
    ] == [(a.step, a.prompt, a.max_new) for a in arrivals]

    model, params = tiny_transformer()
    cfg = ServingConfig(
        page_size=4, n_pages=7, max_batch=2, max_pages_per_request=4,
        ber=1e-3, seed=29, prefill_chunk=4,
    )
    sp_mesh, sp_plain = _spaces(mesh)
    sharded = Engine(model, params, cfg, space=sp_mesh)
    assert sharded._kernel_shard is not None
    plain = Engine(model, params, cfg, space=sp_plain)
    rep_s = drive(sharded, arrivals)
    rep_p = drive(plain, arrivals)
    assert rep_s["token_streams"] == rep_p["token_streams"]
    assert rep_s["tokens_emitted"] == rep_p["tokens_emitted"] > 0


def test_chip_smoke_four_chip_phase(monkeypatch):
    """``chip_smoke.py --chips 4``'s comparison at a tiny f32 size on a
    (4, 1) mesh: the engine's default space with a mesh engages the sharded
    walk, and on the contexts both engines shared its logits match the
    one-device engine's and the gathered jnp path's to f32 rounding."""
    import importlib
    import pathlib
    import sys

    from conftest import tiny_transformer

    monkeypatch.setenv("REPRO_KERNEL_PLANS", "1")    # kernel page scrub on CPU
    monkeypatch.syspath_prepend(str(pathlib.Path(__file__).resolve().parents[2]))
    cs = importlib.import_module("chip_smoke")
    model, params = tiny_transformer()
    mesh4 = make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])
    geometry = dict(page_size=4, n_pages=31, max_batch=2,
                    max_pages_per_request=8, prefill_chunk=8, repair="page")
    prompts = cs.make_prompts(0, model.cfg.vocab, 3, 5, 20)
    out = cs.compare_sharded(model, params, prompts, mesh=mesh4, seed=0,
                             geometry=geometry, max_new=4, interpret=True)
    sys.modules.pop("chip_smoke", None)
    assert out["sharded_kernels"]
    assert out["identical"] == len(prompts)
    assert out["shared_positions"] == [4] * len(prompts)
    assert out["d_shard"] <= 1e-5 and out["d_ref"] <= 1e-4
    assert out["d_shard"] <= cs.SHARD_TOL * out["d_ref"]
