"""Chunked paged prefill + split-K flash decoding (the PR-8 kernel family).

Covers the acceptance contract: prefill-kernel-vs-oracle parity (values
allclose, per-page fatal counters bit-exact) with poisoned pages and ragged
chunk placement; ``Attention.paged_prefill`` parity with the gathered
``decode`` chunk math AND pool-write-set bit-equality (padded rows must not
perturb the pool); split-K vs serial bit-parity over >= 8-page walks
including the ragged null-tail regression (empty splits contribute -inf,
not fill-value mass); engine-level — fused prefill keeps tokens/stats/
bytes/ledger identical to the gathered-prefill arm under injected flips
with ZERO full-view copies, chunked prefill coexists with decode in one
step at token parity, prefix-cache suffix prefills land on the chunked
kernel, split-K decode is token/stats-identical to the serial walk; and the
retirement of the ``pool.fatal_pages`` probe behind a deprecation shim.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_transformer
from repro.core import rules as rules_lib
from repro.kernels import paged_attention as pa
from repro.kernels import ref
from repro.serving import Engine, ServingConfig
from repro.serving.config import ServingConfig as _SC


# ------------------------------------------------------------------ kernels
def _pool(key, P=9, L=2, pg=4, Kh=2, Dh=16):
    k1, k2 = jax.random.split(key)
    k_pages = jax.random.normal(k1, (P, L, pg, Kh, Dh), jnp.float32)
    v_pages = jax.random.normal(k2, (P, L, pg, Kh, Dh), jnp.float32)
    return k_pages, v_pages


@pytest.mark.parametrize("policy,constant", [("zero", 0.0), ("constant", 0.5)])
def test_prefill_kernel_matches_oracle_with_poisoned_pages(policy, constant):
    key = jax.random.PRNGKey(0)
    k_pages, v_pages = _pool(key)
    # chunk of 4 queries per request, ragged placement: request 0 resumes
    # at context position 5, request 1 starts at 0
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 4, 16),
                          jnp.float32)
    k_pages = k_pages.at[2, 1, 1, 0, 3].set(jnp.nan)
    v_pages = v_pages.at[5, 1, 0, 1, 0].set(jnp.inf)
    k_pages = k_pages.at[7, 1, 0, 0, 0].set(jnp.nan)   # unreferenced page
    bt = jnp.asarray([[0, 2, 6], [5, 1, 8]], jnp.int32)
    q_start = jnp.asarray([5, 0], jnp.int32)

    out, page_counts, counts = pa.paged_prefill(
        q, k_pages, v_pages, bt, q_start, layer=1,
        policy=policy, constant=constant,
    )
    ref_out, slot = ref.paged_prefill_ref(
        q, k_pages, v_pages, bt, q_start, layer=1,
        policy=policy, constant=constant,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=1e-5
    )
    ref_pages = np.zeros(9, np.int64)
    np.add.at(ref_pages, np.asarray(bt), np.asarray(slot))
    np.testing.assert_array_equal(np.asarray(page_counts), ref_pages)
    assert int(page_counts[2]) == 1 and int(page_counts[5]) == 1
    assert int(page_counts[7]) == 0                    # never streamed
    assert int(counts[pa.NAN_K]) == 1 and int(counts[pa.INF_V]) == 1
    assert int(counts[pa.EV_TOTAL]) == 2


def test_prefill_kernel_at_starcoder2_widths_repairs_and_counts_once():
    """starcoder2-15b's attention widths at the engine's chunk: 48 query /
    4 KV heads (group 12), head_dim 128, 256-row chunks over 16-token bf16
    pages — 12,288 (C, H) rows a grid cell, the chunk that overflowed the
    32 MiB scoped VMEM.  Ragged placement (one request resumes mid-page,
    one starts at 0, their pages interleaved), a NaN in a K page and an
    Inf in a V page inside the causal horizon: the output matches the
    oracle, and each poisoned page is repaired and counted exactly once."""
    H, Kh, Dh, pg, C, M = 48, 4, 128, 16, 256, 20
    key = jax.random.PRNGKey(31)
    k_pages, v_pages = _pool(key, P=2 * M + 1, L=2, pg=pg, Kh=Kh, Dh=Dh)
    bt = jnp.asarray(np.arange(2 * M).reshape(M, 2).T, jnp.int32)
    q_start = jnp.asarray([37, 0], jnp.int32)
    k_pages = k_pages.at[int(bt[0, 3]), 1, 5, 2, 40].set(jnp.nan)
    v_pages = v_pages.at[int(bt[1, 9]), 1, 11, 3, 7].set(jnp.inf)
    k_pages = k_pages.at[2 * M, 1, 0, 0, 0].set(jnp.nan)   # in no table
    k_pages, v_pages = k_pages.astype(jnp.bfloat16), v_pages.astype(jnp.bfloat16)
    q = jax.random.normal(
        jax.random.fold_in(key, 1), (2, C, H, Dh), jnp.float32
    ).astype(jnp.bfloat16)
    # above the 32 MiB the qwen2 chunk (3,072 rows) compiles under
    assert pa._prefill_vmem_bytes(C, H, Dh, 2) > pa._PREFILL_VMEM_BYTES
    assert pa._prefill_vmem_bytes(C, 12, Dh, 2) == pa._PREFILL_VMEM_BYTES

    out, slot, counts = pa.paged_prefill_raw(
        q, k_pages, v_pages, bt, q_start, jnp.int32(1), policy="zero",
    )
    ref_out, ref_slot = ref.paged_prefill_ref(
        q, k_pages, v_pages, bt, q_start, layer=1, policy="zero",
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=2e-2, rtol=2e-2,
    )
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(ref_slot))
    expect = np.zeros((2, M), np.int32)
    expect[0, 3] = expect[1, 9] = 1
    np.testing.assert_array_equal(np.asarray(slot), expect)
    assert int(counts[pa.NAN_K]) == 1 and int(counts[pa.INF_V]) == 1
    assert int(counts[pa.EV_TOTAL]) == 2
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_prefill_kernel_causal_mask_matches_decode_walk():
    """Row c of a chunk must see exactly the prefix a decode at position
    ``q_start + c`` sees: run the decode kernel once per chunk row and
    compare against the one-shot prefill kernel."""
    key = jax.random.PRNGKey(2)
    k_pages, v_pages = _pool(key, P=6, L=1)
    C = 4
    q = jax.random.normal(jax.random.fold_in(key, 3), (1, C, 4, 16),
                          jnp.float32)
    bt = jnp.asarray([[1, 3, 4]], jnp.int32)
    q_start = jnp.asarray([3], jnp.int32)

    out, _, _ = pa.paged_prefill(
        q, k_pages, v_pages, bt, q_start, layer=0, policy="zero",
    )
    for c in range(C):
        step, _, _ = pa.paged_attention(
            q[:, c], k_pages, v_pages, bt,
            jnp.asarray([3 + c], jnp.int32), layer=0, policy="zero",
        )
        np.testing.assert_allclose(
            np.asarray(out[:, c]), np.asarray(step), atol=1e-5
        )


def test_attention_paged_prefill_matches_gathered_chunk():
    """`Attention.paged_prefill` == `Attention.decode` with an S>1 chunk
    over the gathered view, and the pool write set is bit-identical to the
    gathered path's (padded rows land as duplicates of the last valid row —
    unwritten lanes keep their exact prior bits)."""
    from repro.nn import module as nn_module
    from repro.nn.attention import Attention

    attn = Attention(
        d_model=32, n_heads=4, n_kv=2, head_dim=8, dtype=jnp.float32,
    )
    params = nn_module.init_params(attn.defs(), jax.random.PRNGKey(0))
    B, C, pg, M, P, L = 2, 4, 4, 3, 7, 1
    null = P - 1
    key = jax.random.PRNGKey(7)
    k_pages = jax.random.normal(key, (P, L, pg, 2, 8), jnp.float32)
    v_pages = jax.random.normal(
        jax.random.fold_in(key, 1), (P, L, pg, 2, 8), jnp.float32
    )
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, C, 32), jnp.float32)
    bt = np.asarray([[0, 2, null], [4, 1, null]], np.int32)
    q_start = np.asarray([3, 0], np.int32)
    q_len = np.asarray([4, 2], np.int32)               # request 1 is ragged

    out_p, kp, vp, slot, counts = attn.paged_prefill(
        params, x, k_pages, v_pages, jnp.asarray(bt),
        jnp.asarray(q_start), jnp.asarray(q_len), jnp.zeros((), jnp.int32),
        policy="zero",
        detector_k=rules_lib.Detector(), detector_v=rules_lib.Detector(),
    )

    def gather(leaf):
        v = leaf[bt][:, :, 0]                          # (B, M, pg, K, Dh)
        return v.reshape(B, M * pg, 2, 8)

    cache = {"k": gather(k_pages), "v": gather(v_pages)}
    out_g, new_cache = attn.decode(
        params, x, cache, jnp.asarray(q_start)
    )
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out_p[b, : q_len[b]]),
            np.asarray(out_g[b, : q_len[b]]),
            atol=1e-5,
        )
        # write-set bit-equality on every VALID chunk position...
        for c in range(int(q_len[b])):
            t = int(q_start[b]) + c
            page, off = bt[b][t // pg], t % pg
            np.testing.assert_array_equal(
                np.asarray(kp[page, 0, off]),
                np.asarray(new_cache["k"][b, t]),
            )
            np.testing.assert_array_equal(
                np.asarray(vp[page, 0, off]),
                np.asarray(new_cache["v"][b, t]),
            )
    # ...and bitwise NO change anywhere the chunks did not write
    written = set()
    for b in range(B):
        for c in range(int(q_len[b])):
            t = int(q_start[b]) + c
            written.add((int(bt[b][t // pg]), t % pg))
    mask = np.ones((P, pg), bool)
    for page, off in written:
        mask[page, off] = False
    np.testing.assert_array_equal(
        np.asarray(kp)[:, 0][mask], np.asarray(k_pages)[:, 0][mask]
    )
    np.testing.assert_array_equal(
        np.asarray(vp)[:, 0][mask], np.asarray(v_pages)[:, 0][mask]
    )


def test_splitk_matches_serial_over_wide_walk():
    """>= 8-page block tables through the split-K kernel: outputs allclose
    to the serial walk, per-slot fatal counts and AT_* totals bit-exact."""
    key = jax.random.PRNGKey(5)
    k_pages, v_pages = _pool(key, P=12, L=2, pg=4)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 16), jnp.float32)
    k_pages = k_pages.at[3, 0, 2, 0, 1].set(jnp.nan)
    v_pages = v_pages.at[9, 0, 1, 1, 5].set(jnp.inf)
    bt = jnp.asarray(
        [[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 11, 11, 11, 11]],
        jnp.int32,
    )
    pos = jnp.asarray([31, 14], jnp.int32)

    serial, slot_s, counts_s = pa.paged_attention(
        q, k_pages, v_pages, bt, pos, layer=0, policy="zero",
    )
    for splits in (2, 4, 8):
        split, slot_k, counts_k = pa.paged_attention_splitk(
            q, k_pages, v_pages, bt, pos, splits=splits, layer=0,
            policy="zero",
        )
        np.testing.assert_allclose(
            np.asarray(split), np.asarray(serial), atol=1e-5, rtol=1e-5
        )
        np.testing.assert_array_equal(np.asarray(slot_k), np.asarray(slot_s))
        np.testing.assert_array_equal(
            np.asarray(counts_k), np.asarray(counts_s)
        )


def test_shard_merge_passes_single_owner_splits_through():
    """The sharded walk's merge combines each split's device partials
    before the splits: a split whose pages one device owns reaches the
    split merge bit for bit as the single-device split-K partial, so the
    merged output equals the one-device merge exactly."""
    key = jax.random.PRNGKey(11)
    k_pages, v_pages = _pool(key, P=8)
    k_pages = k_pages.at[4, 1, 2, 0, 3].set(jnp.nan)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 16), jnp.float32)
    # 8 rows over 4 shards of 2: every 2-slot split lies on one shard
    bt = jnp.asarray([[0, 1, 4, 5], [2, 3, 7, 7]], jnp.int32)
    pos = jnp.asarray([13, 6], jnp.int32)
    pos_slot = jnp.broadcast_to(pos[:, None], bt.shape)
    kw = dict(
        splits=2, consts=pa._detector_consts("default", "default",
                                             k_pages.dtype, True),
        policy_k="zero", constant_k=0.0, policy_v="zero", constant_v=0.0,
        interpret=True,
    )
    layer = jnp.int32(1)
    o1, m1, l1, _, _ = pa._splitk_partials(
        q, k_pages, v_pages, bt, pos_slot, layer, **kw
    )
    parts = []
    for lo in range(0, 8, 2):
        owned, bt_local = pa._owned_remap(bt, lo, 2)
        parts.append(pa._splitk_partials(
            q, k_pages[lo:lo + 2], v_pages[lo:lo + 2], bt_local,
            jnp.where(owned, pos_slot, -1), layer, **kw,
        )[:3])
    o, m, l = (jnp.stack(x, axis=1) for x in zip(*parts))
    sharded = pa._shard_merge(jnp.float32, o, m, l)
    one = pa._lse_merge(jnp.float32, o1, m1, l1)
    np.testing.assert_array_equal(
        np.asarray(sharded).view(np.uint32), np.asarray(one).view(np.uint32)
    )


def test_splitk_ragged_null_tail_regression():
    """A request whose valid pages occupy only the FIRST split leaves the
    remaining splits entirely null — those must contribute -inf logits to
    the merge (weight exactly zero), not fill-value probability mass."""
    key = jax.random.PRNGKey(6)
    k_pages, v_pages = _pool(key, P=10, L=1, pg=4)
    null = 9
    # park huge finite garbage in the null page: any leakage of a null
    # split through the merge moves the output far off the serial walk
    k_pages = k_pages.at[null].set(1e4)
    v_pages = v_pages.at[null].set(-1e4)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 16), jnp.float32)
    bt = jnp.asarray(
        [[0, 1, 2, 3, 4, 5, 6, 7],
         [8, null, null, null, null, null, null, null]],
        jnp.int32,
    )
    pos = jnp.asarray([15, 1], jnp.int32)              # request 1: 2 tokens

    serial, slot_s, _ = pa.paged_attention(
        q, k_pages, v_pages, bt, pos, layer=0, policy="zero",
    )
    split, slot_k, _ = pa.paged_attention_splitk(
        q, k_pages, v_pages, bt, pos, splits=4, layer=0, policy="zero",
    )
    np.testing.assert_allclose(
        np.asarray(split), np.asarray(serial), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(slot_k), np.asarray(slot_s))
    assert bool(jnp.isfinite(split).all())
    # the independent oracle agrees
    ref_out, ref_slot = ref.paged_splitk_ref(
        q, k_pages, v_pages, bt, pos, splits=4, layer=0, policy="zero",
    )
    np.testing.assert_allclose(
        np.asarray(split), np.asarray(ref_out), atol=1e-5, rtol=1e-5
    )
    ref_pages = np.zeros(10, np.int64)
    np.add.at(ref_pages, np.asarray(bt), np.asarray(ref_slot))
    np.testing.assert_array_equal(np.asarray(slot_k), ref_pages)


# live-page walk: pages of 16 tokens, 40-slot tables, so splits of 1, 2, 4
# and 5 walk blocks of 10, 10, 10 and 8 pages
_LP_PG, _LP_M = 16, 40


def _live_pool(key, *, Dh=16):
    """Four requests over a 40-slot table: 21 live pages of 24 allocated
    (a multiple of no block size here), one single-token request, an idle
    slot (all null, position 0) and a full table, plus one page no table
    holds.  Returns the pool, the tables, the positions, the spare page
    and the null page."""
    counts = (24, 1, 0, _LP_M)
    P = sum(counts) + 2
    spare, null = P - 2, P - 1
    k_pages, v_pages = _pool(key, P=P, L=2, pg=_LP_PG, Dh=Dh)
    bt = np.full((4, _LP_M), null, np.int32)
    nxt = 0
    for b, n in enumerate(counts):
        bt[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pos = np.asarray([20 * _LP_PG + 5, 0, 0, _LP_M * _LP_PG - 1], np.int32)
    return k_pages, v_pages, jnp.asarray(bt), jnp.asarray(pos), spare, null


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("splits", [1, 2, 4, 5])
def test_splitk_live_walk_matches_oracle_over_ragged_contexts(splits, dtype):
    """The live-page walk against the independent oracle: ragged contexts
    whose live pages do not fill their last block, a one-token request, an
    idle slot and a full table, at several split counts — values allclose,
    per-slot counts exact, and the live walk really blocks several pages.
    A bfloat16 pool is read as words holding two heads' lanes each."""
    key = jax.random.PRNGKey(21)
    k_pages, v_pages, bt, pos, _, _ = _live_pool(key)
    assert pa._splitk_block_pages(_LP_M // splits, _LP_PG) > 1
    k_pages = k_pages.at[int(bt[0, 17]), 1, 3, 1, 2].set(jnp.nan)
    v_pages = v_pages.at[int(bt[3, 39]), 1, 15, 0, 7].set(jnp.inf)
    k_pages, v_pages = k_pages.astype(dtype), v_pages.astype(dtype)
    q = jax.random.normal(
        jax.random.fold_in(key, 1), (4, 4, 16), jnp.float32
    ).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2

    out, slot, counts = pa.paged_attention_splitk_raw(
        q, k_pages, v_pages, bt, pos, jnp.int32(1), splits=splits,
        policy="zero",
    )
    ref_out, ref_slot = ref.paged_splitk_ref(
        q, k_pages, v_pages, bt, pos, splits=splits, layer=1, policy="zero",
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=tol, rtol=tol,
    )
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(ref_slot))
    assert int(slot[0, 17]) == 1 and int(slot[3, 39]) == 1
    assert int(counts[pa.EV_TOTAL]) == 2
    # the one-token request attends to exactly its one key
    v0 = np.asarray(v_pages[int(bt[1, 0]), 1, 0], np.float32)  # (Kh, Dh)
    np.testing.assert_array_equal(
        np.asarray(out[1], np.float32), np.repeat(v0, 2, axis=0)
    )


@pytest.mark.parametrize("detect", [True, False])
def test_splitk_never_reads_past_the_context(detect):
    """NaN parked in the null page and in an allocated page past the
    request's position: neither is read, so the output stays finite even
    with detection off (an unrepaired NaN that were read would poison the
    context through 0 * NaN), and both slots count 0."""
    key = jax.random.PRNGKey(22)
    k_pages, v_pages, bt, pos, spare, null = _live_pool(key)
    # allocated past request 0's end, in the block of its last live page
    bt = bt.at[0, 21].set(spare)
    k_pages = k_pages.at[null].set(jnp.nan).at[spare].set(jnp.nan)
    v_pages = v_pages.at[null].set(jnp.nan).at[spare].set(jnp.nan)
    q = jax.random.normal(jax.random.fold_in(key, 1), (4, 4, 16), jnp.float32)
    det = "default" if detect else None

    out, slot, counts = pa.paged_attention_splitk_raw(
        q, k_pages, v_pages, bt, pos, jnp.int32(0), splits=2,
        detector_k=det, detector_v=det,
    )
    # the idle slot (b = 2, position 0) reads the null page's slot 0
    live_rows = [0, 1, 3]
    assert bool(jnp.isfinite(out[jnp.asarray(live_rows)]).all())
    slot = np.asarray(slot)
    assert (slot[0, 21:] == 0).all()
    assert (slot[1, 1:] == 0).all() and (slot[2, 1:] == 0).all()
    assert int(counts[pa.EV_TOTAL]) == (1 if detect else 0)
    assert slot.sum() == (int(slot[2, 0]) if detect else 0)
    ref_out, ref_slot = ref.paged_splitk_ref(
        q, k_pages, v_pages, bt, pos, splits=2, layer=0,
        detector_k=det, detector_v=det,
    )
    np.testing.assert_array_equal(slot, np.asarray(ref_slot))
    np.testing.assert_allclose(
        np.asarray(out)[live_rows], np.asarray(ref_out)[live_rows],
        atol=1e-5, rtol=1e-5,
    )


def test_splitk_repairs_live_page_in_vmem_and_counts_its_slot():
    """A NaN in a live page (inside the context, in the middle of a block)
    is repaired on read — the output equals the walk over a pool whose lane
    was already zero — and counted at its exact (b, slot), nowhere else."""
    key = jax.random.PRNGKey(23)
    k_pages, v_pages, bt, pos, _, _ = _live_pool(key)
    page = int(bt[3, 13])
    q = jax.random.normal(jax.random.fold_in(key, 1), (4, 4, 16), jnp.float32)
    clean_k = k_pages.at[page, 0, 6, 0, 9].set(0.0)
    clean_v = v_pages.at[page, 0, 2, 1, 4].set(0.0)
    bad_k = k_pages.at[page, 0, 6, 0, 9].set(jnp.nan)
    bad_v = v_pages.at[page, 0, 2, 1, 4].set(-jnp.inf)

    want, _, _ = pa.paged_attention_splitk_raw(
        q, clean_k, clean_v, bt, pos, jnp.int32(0), splits=4,
    )
    got, slot, counts = pa.paged_attention_splitk_raw(
        q, bad_k, bad_v, bt, pos, jnp.int32(0), splits=4,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    expect = np.zeros((4, _LP_M), np.int32)
    expect[3, 13] = 2
    np.testing.assert_array_equal(np.asarray(slot), expect)
    assert int(counts[pa.NAN_K]) == 1 and int(counts[pa.INF_V]) == 1
    assert int(counts[pa.EV_K]) == 1 and int(counts[pa.EV_V]) == 1
    assert int(counts[pa.EV_TOTAL]) == 1


# ------------------------------------------------------------------ engine
@pytest.fixture(scope="module")
def model_params():
    return tiny_transformer()


def _engine(model, params, *, ber=0.0, seed=3, max_new=6, n_req=6, **kw):
    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=10, max_batch=4, max_pages_per_request=5,
        ber=ber, sweep_interval=8, sweep_pages=2, seed=seed, **kw,
    ))
    for i in range(n_req):
        prompt = jax.random.randint(jax.random.PRNGKey(i), (5 + i % 3,), 1, 96)
        eng.add_request(prompt, max_new=max_new)
    return eng


def test_fused_prefill_bit_identical_to_gathered_under_flips(model_params):
    """The prefill acceptance bar: tokens, unified stats, scrubbed bytes,
    and the per-page fault ledger of the fused-prefill engine are identical
    to the gathered-prefill arm under the same injected bit-flips — and the
    fused engine issues ZERO full-view pool copies across the whole run."""
    model, params = model_params
    fused = _engine(model, params, ber=1e-3)
    assert fused._prefill_fn is not None
    res_f = fused.run()

    legacy = _engine(model, params, ber=1e-3, paged_prefill="off")
    assert legacy._prefill_fn is None and legacy._paged_fn is not None
    res_g = legacy.run()

    assert fused.stats_dict()["events"] > 0            # faults actually fired
    for rid in res_f:
        assert res_f[rid]["tokens"] == res_g[rid]["tokens"]
    assert fused.stats_dict() == legacy.stats_dict()
    assert fused.rule_stats() == legacy.rule_stats()
    assert fused.pool.scrubbed_bytes == legacy.pool.scrubbed_bytes
    np.testing.assert_array_equal(
        fused.pool.page_events, legacy.pool.page_events
    )
    assert fused.pool.n_gathers == 0
    assert fused.pool.n_scatters == 0
    assert legacy.pool.n_gathers > 0                   # the copies it retired


def test_chunked_prefill_coexists_with_decode(model_params):
    """vllm-style mixed batching: with ``prefill_chunk`` set, a step can
    stream one request's prompt chunk AND decode another request's token —
    and the chunked run emits exactly the tokens of the unchunked one."""
    model, params = model_params
    whole = Engine(model, params, ServingConfig(
        page_size=4, n_pages=12, max_batch=2, max_pages_per_request=6,
    ))
    chunked = Engine(model, params, ServingConfig(
        page_size=4, n_pages=12, max_batch=2, max_pages_per_request=6,
        prefill_chunk=3,
    ))
    prompts = [[5, 6, 7], [11, 3, 9, 2, 8, 4, 1, 7, 6, 2]]
    for eng in (whole, chunked):
        for p in prompts:
            eng.add_request(p, max_new=6)

    res_w = whole.run()
    mixed_steps = 0
    outs = []
    while chunked.has_work:
        out = chunked.step()
        outs.append(out)
        if chunked._prefilling and out["emitted"]:
            mixed_steps += 1                   # a chunk AND a token together
    res_c = chunked.results
    for rid in res_w:
        assert res_c[rid]["tokens"] == res_w[rid]["tokens"]
    # request 0 (3 tokens) prefills in one chunk and decodes while request
    # 1 (10 tokens) is still streaming chunks
    assert mixed_steps > 0
    assert chunked.pool.n_gathers == 0 and chunked.pool.n_scatters == 0


def test_prefix_cache_suffix_prefill_on_chunked_kernel(model_params):
    """A cache hit prefills only the suffix — and that suffix pass runs on
    the chunked paged kernel, not a gathered view."""
    model, params = model_params
    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=16, max_batch=2, max_pages_per_request=4,
        prefix_cache=True,
    ))
    prefix = [7, 3, 9, 2, 11, 5, 8, 4]                 # two full pages
    r0 = eng.add_request(prefix + [21], max_new=3)
    eng.run()
    r1 = eng.add_request(prefix + [33, 14], max_new=3)
    res = eng.run()
    assert len(res[r1]["generated"]) == 3
    assert eng.cache_stats()["prefill_tokens_saved"] == 8
    assert eng.pool.n_gathers == 0 and eng.pool.n_scatters == 0
    # parity: same second request through a cache-less engine
    ref_eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=16, max_batch=2, max_pages_per_request=4,
    ))
    rr = ref_eng.add_request(prefix + [33, 14], max_new=3)
    assert ref_eng.run()[rr]["tokens"] == res[r1]["tokens"]


def test_splitk_engine_parity_under_flips(model_params):
    """Split-K decode (auto-engaged at an 8-page block table) is token- and
    stats-identical to the serial walk under injected flips."""
    model, params = model_params

    def build(split_k):
        eng = Engine(model, params, ServingConfig(
            page_size=4, n_pages=12, max_batch=2, max_pages_per_request=8,
            ber=1e-3, seed=5, sweep_interval=8, sweep_pages=2,
            split_k=split_k,
        ))
        prompt = jax.random.randint(jax.random.PRNGKey(9), (26,), 1, 96)
        eng.add_request(prompt, max_new=6)             # context spans 8 pages
        eng.add_request([4, 17, 2], max_new=6)
        return eng

    split = build(0)                                   # auto: M=8 -> 4 splits
    assert split._split_k == 4
    res_s = split.run()

    serial = build(1)
    assert serial._split_k == 1
    res_1 = serial.run()

    assert split.stats_dict()["events"] > 0
    for rid in res_s:
        assert res_s[rid]["tokens"] == res_1[rid]["tokens"]
    assert split.stats_dict() == serial.stats_dict()
    assert split.pool.scrubbed_bytes == serial.pool.scrubbed_bytes
    np.testing.assert_array_equal(
        split.pool.page_events, serial.pool.page_events
    )
    assert split.pool.n_gathers == 0 and split.pool.n_scatters == 0


def test_decode_walk_counters(model_params):
    """``decode_pages_walked`` sums each decoding request's page count over
    the decode steps; ``decode_page_slots`` is steps x B x M — counted on
    the host from the batch the engine builds anyway."""
    model, params = model_params
    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=12, max_batch=2, max_pages_per_request=8,
    ))
    prompt = jax.random.randint(jax.random.PRNGKey(9), (14,), 1, 96)
    eng.add_request(prompt, max_new=6)
    eng.add_request([4, 17, 2], max_new=4)
    want = {"pages": 0, "steps": 0}
    build = eng._decode_batch

    def spy(reqs):
        want["pages"] += sum(len(r.pages) for r in reqs)
        want["steps"] += 1
        return build(reqs)

    eng._decode_batch = spy
    eng.run()
    m = eng.metrics()
    assert want["steps"] > 0
    assert m["decode_pages_walked"] == want["pages"]
    assert m["decode_page_slots"] == want["steps"] * 2 * 8
    assert 0 < m["decode_pages_walked"] < m["decode_page_slots"]


def test_fatal_pages_probe_is_deprecated(model_params):
    """Satellite: the probe survives only as a compat shim — calling it
    warns, and a default fused engine run never triggers it."""
    model, params = model_params
    eng = _engine(model, params, ber=1e-3, n_req=2, max_new=3)
    with pytest.warns(DeprecationWarning, match="fatal_pages is deprecated"):
        eng.pool.fatal_pages([0, 1])
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        eng.run()                                      # fused paths: no probe


def test_serving_config_split_k_resolution():
    base = dict(page_size=4, n_pages=32)
    assert _SC(**base, max_pages_per_request=8).resolve_split_k() == 4
    assert _SC(**base, max_pages_per_request=5).resolve_split_k() == 1
    assert _SC(**base, max_pages_per_request=12).resolve_split_k() == 4
    assert _SC(page_size=16, n_pages=2560,
               max_pages_per_request=160).resolve_split_k() == 4
    assert _SC(**base, max_pages_per_request=9).resolve_split_k() == 3
    assert _SC(**base, max_pages_per_request=8, split_k=1).resolve_split_k() == 1
    assert _SC(**base, max_pages_per_request=8, split_k=3).resolve_split_k() == 2
    assert _SC(**base, max_pages_per_request=8, split_k=16).resolve_split_k() == 8
    assert _SC(**base, max_pages_per_request=6, split_k=6).resolve_split_k() == 6
    with pytest.raises(ValueError):
        _SC(split_k=-1)
    with pytest.raises(ValueError):
        _SC(prefill_chunk=-2)
    with pytest.raises(ValueError):
        _SC(paged_prefill="sometimes")
