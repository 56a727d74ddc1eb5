"""Compile guard for the serving hot path on TPU v5e.

Interpret mode (every other kernel test) never checks the chip's tiling
and memory-space rules.  Here each Pallas kernel of the serving path is
compiled with ``interpret=False`` for a described, not attached, v5e chip
at qwen2-1.5b attention widths (bf16, 12 query / 2 KV heads, head_dim
128, 16-token pages) and the serving geometry ``chip_smoke.py`` runs, and
the compiled HLO must hold the kernel (``tpu_custom_call``).  The chunked
prefill and split-K decode also compile at starcoder2-15b's widths (48 /
4 heads, a 10-layer pool) and the geometry of its benchmark cell: a
256-row chunk there holds 12,288 (C, H) rows in one grid cell.  The
four-chip cases compile the shard_map wrappers over a 4x1 mesh of the
described devices.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, so every worker must
collect the same tests and only the one running this file loads it.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.serving.config import ServingConfig

# qwen2-1.5b attention widths and the chip_smoke.py pool geometry
H, KH, DH, PG = 12, 2, 128, 16
B, M, N_ROWS, LAYERS = 8, 64, 1024, 28
CHUNK = 256
BF16, I32 = jnp.bfloat16, jnp.int32


def _splits(n_rows, b, m):
    """The engine's split count for this table width (its auto rule)."""
    return ServingConfig(
        page_size=PG, n_pages=n_rows - 1, max_batch=b, max_pages_per_request=m,
    ).resolve_split_k()


SPLITS = _splits(N_ROWS, B, M)
# (query heads, KV heads, pool rows, layers, batch, table width) per model;
# starcoder2-15b.stage4 as its benchmark cell serves it
WIDTHS = {
    "qwen2": (H, KH, N_ROWS, LAYERS, B, M),
    "starcoder2": (48, 4, 2049, 10, 8, 256),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from repro.launch.mesh import make_mesh

    return make_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])


def _pa():
    return importlib.import_module("repro.kernels.paged_attention")


def _single_chip_case(name, one_chip, widths="qwen2"):
    """(jitted fn, abstract args) of one single-chip kernel call."""
    pa = _pa()
    scrub = importlib.import_module("repro.kernels.scrub")
    mm = importlib.import_module("repro.kernels.repair_matmul")
    H, KH, N_ROWS, LAYERS, B, M = WIDTHS[widths]
    SPLITS = _splits(N_ROWS, B, M)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = s((N_ROWS, LAYERS, PG, KH, DH), BF16)
    if name == "paged_decode":
        return (
            lambda q, k, v, bt, pos, lay: pa.paged_attention_raw(
                q, k, v, bt, pos, lay, interpret=False
            ),
            (s((B, H, DH), BF16), pages, pages, s((B, M), I32),
             s((B,), I32), s((), I32)),
        )
    if name == "paged_decode_splitk":
        return (
            lambda q, k, v, bt, pos, lay: pa.paged_attention_splitk_raw(
                q, k, v, bt, pos, lay, splits=SPLITS, interpret=False
            ),
            (s((B, H, DH), BF16), pages, pages, s((B, M), I32),
             s((B,), I32), s((), I32)),
        )
    if name == "paged_prefill":
        return (
            lambda q, k, v, bt, qs, lay: pa.paged_prefill_raw(
                q, k, v, bt, qs, lay, interpret=False
            ),
            (s((1, CHUNK, H, DH), BF16), pages, pages, s((1, M), I32),
             s((1,), I32), s((), I32)),
        )
    if name == "scrub":
        return (
            lambda x: scrub.scrub(x, interpret=False),
            (pages,),
        )
    if name == "scrub_pages":
        return (
            lambda x, ids, n: scrub.scrub_pages(
                x, ids, n_valid=n, interpret=False
            ),
            (pages, s((16,), I32), s((), I32)),
        )
    if name == "repair_matmul":
        return (
            lambda a, b: mm.repair_matmul_raw(a, b, interpret=False),
            (s((512, 1536), BF16), s((1536, 8960), BF16)),
        )
    raise ValueError(name)


@pytest.mark.parametrize(
    "name,widths",
    [
        pytest.param(name, "qwen2", id=name)
        for name in ("paged_decode", "paged_decode_splitk", "paged_prefill",
                     "scrub", "scrub_pages", "repair_matmul")
    ] + [
        pytest.param(name, "starcoder2", id=f"{name}-starcoder2")
        for name in ("paged_decode_splitk", "paged_prefill")
    ],
)
def test_kernel_compiles_for_v5e(name, widths, one_chip):
    fn, args = _single_chip_case(name, one_chip, widths)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernels stream HBM blocks: no whole-pool temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def _sharded_case(name, mesh):
    pa = _pa()
    scrub = importlib.import_module("repro.kernels.scrub")
    rep, pages_spec = PartitionSpec(), PartitionSpec("data")

    def s(shape, dtype, spec=rep):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    pages = s((N_ROWS, LAYERS, PG, KH, DH), BF16, pages_spec)
    if name == "paged_decode_sharded":
        return (
            lambda q, k, v, bt, pos, lay: pa.paged_attention_sharded(
                q, k, v, bt, pos, lay, mesh=mesh, axis="data",
                splits=SPLITS, interpret=False,
            ),
            (s((B, H, DH), BF16), pages, pages, s((B, M), I32),
             s((B,), I32), s((), I32)),
        )
    if name == "paged_prefill_sharded":
        return (
            lambda q, k, v, bt, qs, lay: pa.paged_prefill_sharded(
                q, k, v, bt, qs, lay, mesh=mesh, axis="data",
                interpret=False,
            ),
            (s((1, CHUNK, H, DH), BF16), pages, pages, s((1, M), I32),
             s((1,), I32), s((), I32)),
        )
    if name == "scrub_sharded":
        return (
            lambda x: scrub.scrub_sharded(x, mesh, pages_spec, interpret=False),
            (pages,),
        )
    raise ValueError(name)


@pytest.mark.parametrize(
    "name",
    ["paged_decode_sharded", "paged_prefill_sharded", "scrub_sharded"],
)
def test_sharded_wrapper_compiles_for_4_chips(name, mesh4):
    fn, args = _sharded_case(name, mesh4)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # counters are reduced across the page shards, never left per-device
    assert "all-reduce" in text


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_serving_step_compiles_at_full_size(kind, chips, topo, monkeypatch):
    """The engine's fused decode / chunked-prefill programs for full-size
    qwen2-1.5b (28 layers, vocab 151936) on one chip, and over a pool
    sharded on a (4, 1) mesh (the device-local shard_map walk): kernels
    compiled rather than interpreted, the donated pool updated in place,
    and everything within one chip's 16 GB."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import stats as stats_lib
    from repro.core.rules import Detector
    from repro.distributed import sharding as sh
    from repro.kernels import common
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.nn import module
    from repro.runtime import ApproxConfig

    # this process's backend is the CPU, so steer the kernels' own
    # interpret choice to the chip's
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_config("qwen2-1.5b"), repair=ApproxConfig(mode="off")
    )
    model = build_model(cfg)
    abstract_params = model.abstract_params()
    # ServingConfig(n_pages=1023) allocates 1024 rows: one is the null page
    abstract_pool = module.abstract_params(model.paged_cache_defs(N_ROWS, PG))
    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        rep, shard = one, None
        params_sh = jax.tree.map(lambda _: one, abstract_params)
        pool_sh = jax.tree.map(lambda _: one, abstract_pool)
    else:
        mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
        rules = sh.rules_for_mesh(mesh)
        rep, shard = NamedSharding(mesh, PartitionSpec()), (mesh, "data")
        params_sh = sh.tree_shardings(
            abstract_params, model.logical_axes(), mesh, rules
        )
        pool_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, sh.spec_for_leaf(
                ("page",) + (None,) * (a.ndim - 1), a.shape, mesh, rules
            )),
            abstract_pool,
        )

    def place(tree, shardings):
        return jax.tree.map(
            lambda a, sd: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sd),
            tree, shardings,
        )

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = place(abstract_params, params_sh)
    pool = place(abstract_pool, pool_sh)
    stats = jax.tree.map(
        lambda a: s(a.shape, a.dtype), jax.eval_shape(stats_lib.zeros)
    )
    repair = dict(
        detectors={"k": Detector(), "v": Detector()},
        fills={"k": ("zero", 0.0), "v": ("zero", 0.0)},
        shard=shard,
    )
    if kind == "decode":
        def step(params, pool, tokens, bt, pos, stats):
            logits, pool, slots, counts = model.serve_step_paged(
                params, pool, {"tokens": tokens}, bt, pos, split_k=SPLITS,
                **repair,
            )
            return jnp.argmax(logits[:, -1], axis=-1), pool, slots, counts

        args = (s((B, 1), I32), s((B, M), I32), s((B,), I32))
    else:
        def step(params, pool, tokens, bt, q_start, q_len, stats):
            logits, pool, slots, counts = model.prefill_paged(
                params, pool, {"tokens": tokens}, bt, q_start, q_len, **repair
            )
            return jnp.argmax(logits[:, -1], axis=-1), pool, slots, counts

        args = (s((1, CHUNK), I32), s((1, M), I32), s((1,), I32), s((1,), I32))

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, *args, stats
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod((N_ROWS, LAYERS, PG, KH, DH))) * 2 // chips
    assert mem.alias_size_in_bytes >= pool_bytes       # pool donated in place
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
