"""Paged-attention Pallas kernel + the fused serving decode path.

Covers the PR acceptance contract: kernel-vs-oracle parity (values allclose,
per-page fatal counters bit-exact) including injected NaN/Inf pages and
null-page tail masking; `Attention.paged_decode` parity with the gathered
`decode`; engine-level — fused decode issues ZERO full-view pool copies
while tokens, stats, byte accounting, and the per-page fault ledger stay
identical to the PR-4 gathered path under injected bit-flips; plan-level —
the `kernel` placement lowers tree scrubs through the Pallas kernels with
bit parity against the jnp path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_transformer
from repro.core import rules as rules_lib
from repro.core import stats as stats_lib
from repro.kernels import paged_attention as pa
from repro.kernels import ref
from repro.runtime import ApproxConfig, ApproxSpace
from repro.serving import Engine, ServingConfig, engine_space


# ------------------------------------------------------------------ kernel
def _pool(key, P=9, L=2, pg=4, Kh=2, Dh=16):
    k1, k2 = jax.random.split(key)
    k_pages = jax.random.normal(k1, (P, L, pg, Kh, Dh), jnp.float32)
    v_pages = jax.random.normal(k2, (P, L, pg, Kh, Dh), jnp.float32)
    return k_pages, v_pages


@pytest.mark.parametrize("policy,constant", [("zero", 0.0), ("constant", 0.5)])
def test_kernel_matches_oracle_with_poisoned_pages(policy, constant):
    key = jax.random.PRNGKey(0)
    k_pages, v_pages = _pool(key)
    q = jax.random.normal(jax.random.fold_in(key, 1), (3, 4, 16), jnp.float32)
    # poison pages the block tables reference AND one they do not
    k_pages = k_pages.at[2, 1, 1, 0, 3].set(jnp.nan)
    v_pages = v_pages.at[5, 1, 0, 1, 0].set(jnp.inf)
    k_pages = k_pages.at[7, 1, 0, 0, 0].set(jnp.nan)   # unreferenced page
    bt = jnp.asarray([[0, 2, 8], [5, 8, 8], [8, 8, 8]], jnp.int32)
    pos = jnp.asarray([9, 5, 0], jnp.int32)

    out, page_counts, counts = pa.paged_attention(
        q, k_pages, v_pages, bt, pos, layer=1,
        policy=policy, constant=constant,
    )
    ref_out, slot = ref.paged_attention_ref(
        q, k_pages, v_pages, bt, pos, layer=1,
        policy=policy, constant=constant,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=1e-5
    )
    ref_pages = np.zeros(9, np.int64)
    np.add.at(ref_pages, np.asarray(bt), np.asarray(slot))
    np.testing.assert_array_equal(np.asarray(page_counts), ref_pages)
    # fatal pages 2 (NaN-K) and 5 (Inf-V) detected; unreferenced page 7 not
    assert int(page_counts[2]) == 1 and int(page_counts[5]) == 1
    assert int(page_counts[7]) == 0
    # AT_* layout totals
    assert int(counts[pa.NAN_K]) == 1 and int(counts[pa.INF_V]) == 1
    assert int(counts[pa.EV_TOTAL]) == 2


def test_kernel_per_operand_fills_match_oracle():
    """Per-tile operand-indexed fill selection: K repairs with zero, V with
    a constant — one kernel call, bit-exact against the oracle given the
    same per-operand fills."""
    key = jax.random.PRNGKey(11)
    k_pages, v_pages = _pool(key)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 16), jnp.float32)
    k_pages = k_pages.at[2, 1, 1, 0, 3].set(jnp.nan)
    v_pages = v_pages.at[5, 1, 0, 1, 0].set(jnp.inf)
    bt = jnp.asarray([[0, 2, 8], [5, 8, 8]], jnp.int32)
    pos = jnp.asarray([9, 3], jnp.int32)

    out, page_counts, counts = pa.paged_attention(
        q, k_pages, v_pages, bt, pos, layer=1,
        policy_k="zero", constant_k=0.0,
        policy_v="constant", constant_v=0.75,
    )
    ref_out, slot = ref.paged_attention_ref(
        q, k_pages, v_pages, bt, pos, layer=1,
        policy_k="zero", constant_k=0.0,
        policy_v="constant", constant_v=0.75,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=1e-5)
    assert int(page_counts[2]) == 1 and int(page_counts[5]) == 1
    # a mixed-fill call must differ from the all-zero-fill one on the V
    # operand (the Inf lane sits at a position the second request attends)
    out_zero, _, _ = pa.paged_attention(
        q, k_pages, v_pages, bt, pos, layer=1, policy="zero",
    )
    assert not np.allclose(np.asarray(out), np.asarray(out_zero))


def test_kernel_null_tail_masking():
    """Null-padded tail slots must not influence the output: garbage (even
    huge finite values) parked in the null page stays masked by position."""
    key = jax.random.PRNGKey(3)
    k_pages, v_pages = _pool(key, P=5, L=1, pg=4)
    null = 4
    k_pages = k_pages.at[null].set(1e9)
    v_pages = v_pages.at[null].set(-1e9)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, 16), jnp.float32)
    pos = jnp.asarray([6], jnp.int32)                # 7 valid positions

    bt_padded = jnp.asarray([[1, 2, null]], jnp.int32)
    out_p, _, _ = pa.paged_attention(
        q, k_pages, v_pages, bt_padded, pos, layer=0, policy="zero",
    )
    # oracle over only the real pages (no padding at all)
    out_ref, _ = ref.paged_attention_ref(
        q, k_pages, v_pages, jnp.asarray([[1, 2]], jnp.int32), pos,
        layer=0, policy="zero",
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_ref), atol=1e-5
    )


def test_kernel_detector_none_is_bit_transparent():
    """A ``None`` detector row disables repair entirely: NaNs flow through
    (the exact-region / non-reactive-rule case) and nothing is counted."""
    key = jax.random.PRNGKey(4)
    k_pages, v_pages = _pool(key, P=4, L=1)
    k_pages = k_pages.at[1, 0, 0, 0, 0].set(jnp.nan)
    q = jax.random.normal(jax.random.fold_in(key, 9), (1, 4, 16), jnp.float32)
    bt = jnp.asarray([[1, 3]], jnp.int32)
    pos = jnp.asarray([5], jnp.int32)
    out, page_counts, counts = pa.paged_attention(
        q, k_pages, v_pages, bt, pos, layer=0,
        detector_k=None, detector_v=None,
    )
    assert int(np.asarray(page_counts).sum()) == 0
    assert int(np.asarray(counts).sum()) == 0
    assert not bool(jnp.isfinite(out).all())         # the NaN was consumed


def test_paged_decode_matches_gathered_decode():
    """`Attention.paged_decode` == `Attention.decode` over the gathered view
    on clean pools: same new-KV write, same tokens-level context math."""
    from repro.nn import module as nn_module
    from repro.nn.attention import Attention

    attn = Attention(
        d_model=32, n_heads=4, n_kv=2, head_dim=8, dtype=jnp.float32,
    )
    params = nn_module.init_params(attn.defs(), jax.random.PRNGKey(0))
    B, pg, M, P, L = 2, 4, 3, 7, 1
    null = P - 1
    key = jax.random.PRNGKey(7)
    k_pages = jax.random.normal(key, (P, L, pg, 2, 8), jnp.float32)
    v_pages = jax.random.normal(
        jax.random.fold_in(key, 1), (P, L, pg, 2, 8), jnp.float32
    )
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, 32), jnp.float32)
    bt = np.asarray([[0, 2, null], [4, null, null]], np.int32)
    pos = np.asarray([6, 2], np.int32)

    out_p, kp, vp, slot, counts = attn.paged_decode(
        params, x, k_pages, v_pages, jnp.asarray(bt), jnp.asarray(pos),
        jnp.zeros((), jnp.int32), policy="zero",
        detector_k=rules_lib.Detector(), detector_v=rules_lib.Detector(),
    )

    # gathered reference: build the contiguous per-request view by hand
    def gather(leaf):
        v = leaf[bt][:, :, 0]                       # (B, M, pg, K, Dh)
        return v.reshape(B, M * pg, 2, 8)

    cache = {"k": gather(k_pages), "v": gather(v_pages)}
    out_g, new_cache = attn.decode(params, x, cache, jnp.asarray(pos))
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_g), atol=1e-5
    )
    # the single-slot write landed where the gathered path wrote it
    for b in range(B):
        page, off = bt[b][pos[b] // pg], pos[b] % pg
        np.testing.assert_allclose(
            np.asarray(kp[page, 0, off]),
            np.asarray(new_cache["k"][b, pos[b]]),
            atol=1e-6,
        )


# ------------------------------------------------------------------ engine
@pytest.fixture(scope="module")
def model_params():
    return tiny_transformer()


def _engine(model, params, *, ber, repair="page", seed=3, max_new=6,
            space=None):
    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=10, max_batch=4, max_pages_per_request=5,
        repair=repair, ber=ber, sweep_interval=8, sweep_pages=2, seed=seed,
    ), space=space)
    for i in range(8):
        prompt = jax.random.randint(jax.random.PRNGKey(i), (5 + i % 3,), 1, 96)
        eng.add_request(prompt, max_new=max_new)
    return eng


def test_engine_decode_issues_zero_pool_copies(model_params):
    """The acceptance criterion: with the full kernel family engaged the
    engine never gathers/scatters a full view — admission, prefill AND
    decode all run straight off the pool."""
    model, params = model_params
    eng = Engine(model, params, ServingConfig(
        page_size=4, n_pages=8, max_batch=2, max_pages_per_request=4,
    ))
    assert eng.paged_plan is not None and eng._paged_fn is not None
    assert eng._prefill_fn is not None
    rid = eng.add_request([5, 6, 7], max_new=8)
    results = eng.run()
    assert len(results[rid]["generated"]) == 8
    assert eng.pool.n_gathers == 0
    assert eng.pool.n_scatters == 0
    assert eng.metrics()["paged_decode"] is True
    assert eng.metrics()["paged_prefill"] is True


def test_fused_path_bit_identical_to_gathered_under_flips(model_params):
    """Tokens, unified stats, scrubbed bytes, and the per-page fault ledger
    of the fused path are identical to the PR-4 gathered path under the
    same injected bit-flips (same seed => same fault exposure).

    Pinned to the NaN/Inf pool rule: on the fused path empty decode slots
    write padding K/V into the null page, which the gathered path leaves
    alone, and the default range guard counts top-exponent flips of those
    padding values — one path's null page would be scrubbed more often."""
    model, params = model_params

    def nan_inf():
        return ApproxSpace(engine_space(model).config, max_magnitude=None)

    fused = _engine(model, params, ber=1e-3, space=nan_inf())
    assert fused._paged_fn is not None
    res_f = fused.run()

    legacy = _engine(model, params, ber=1e-3, space=nan_inf())
    legacy._paged_fn = None                      # force the gathered path
    res_g = legacy.run()

    assert fused.stats_dict()["events"] > 0      # faults actually fired
    for rid in res_f:
        assert res_f[rid]["tokens"] == res_g[rid]["tokens"]
    assert fused.stats_dict() == legacy.stats_dict()
    assert fused.pool.scrubbed_bytes == legacy.pool.scrubbed_bytes
    np.testing.assert_array_equal(
        fused.pool.page_events, legacy.pool.page_events
    )
    # and the fused engine really skipped the decode copies
    assert fused.pool.n_gathers < legacy.pool.n_gathers


def test_fused_eligibility_falls_back(model_params):
    """Configurations the kernel cannot reproduce bit-for-bit keep the
    gathered path: neighbor_mean fill, repair="off"."""
    model, params = model_params
    cfg = ServingConfig(page_size=4, n_pages=8, max_batch=2,
                        max_pages_per_request=4)
    nm = Engine(model, params, cfg, space=ApproxSpace(
        ApproxConfig(mode="memory", policy="neighbor_mean",
                     max_magnitude=None)
    ))
    assert nm.paged_plan is None
    off = Engine(model, params, dataclasses.replace(cfg, repair="off"))
    assert off.paged_plan is None
    # and the fallback still serves correctly
    rid = nm.add_request([4, 5], max_new=3)
    assert len(nm.run()[rid]["generated"]) == 3


def test_fused_respects_reactive_rule_gating(model_params):
    """A pool rule that never fires reactively gets a ``None`` detector in
    the fused plan — the kernel reads it bit-transparently, matching the
    probe gate of ``pool.fatal_pages``."""
    model, params = model_params
    rules = rules_lib.RuleSet(entries=(
        (r".*", rules_lib.RepairRule(fill="zero", trigger="on-read")),
    ))
    eng = Engine(
        model, params,
        ServingConfig(page_size=4, n_pages=8, max_batch=2,
                      max_pages_per_request=4),
        space=ApproxSpace(ApproxConfig(mode="memory", rules=rules)),
    )
    assert eng.paged_plan is not None
    assert all(d is None for d in eng.paged_plan.detectors.values())


def test_mixed_fill_ruleset_stays_fused(model_params):
    """A RuleSet whose K and V rules fill differently no longer forces the
    gathered fallback: the plan carries per-leaf fills and the fused path
    stays token-identical to the gathered one under injected flips."""
    model, params = model_params
    rules = rules_lib.RuleSet(entries=(
        (r".*/k$", rules_lib.RepairRule(fill="zero")),
        (r".*", rules_lib.RepairRule(fill=0.5)),
    ))

    def build():
        eng = Engine(
            model, params,
            ServingConfig(page_size=4, n_pages=10, max_batch=4,
                          max_pages_per_request=5, ber=1e-3, seed=3,
                          sweep_interval=8, sweep_pages=2),
            space=ApproxSpace(ApproxConfig(mode="memory", rules=rules)),
        )
        for i in range(8):
            prompt = jax.random.randint(
                jax.random.PRNGKey(i), (5 + i % 3,), 1, 96
            )
            eng.add_request(prompt, max_new=6)
        return eng

    fused = build()
    assert fused.paged_plan is not None and fused._paged_fn is not None
    assert fused.paged_plan.fills == {
        "k": ("zero", 0.0), "v": ("constant", 0.5),
    }
    res_f = fused.run()

    legacy = build()
    legacy._paged_fn = None                      # force the gathered path
    res_g = legacy.run()

    assert fused.stats_dict()["events"] > 0      # mixed fills actually fired
    for rid in res_f:
        assert res_f[rid]["tokens"] == res_g[rid]["tokens"]
    assert fused.stats_dict() == legacy.stats_dict()


# ----------------------------------------------------------- plan placement
def test_kernel_placement_bit_parity(monkeypatch):
    """REPRO_KERNEL_PLANS=1 routes tree-scope scrubs through the Pallas
    kernels (interpret mode on CPU) with values and stats bit-identical to
    the jnp lowering; non-representable fills keep the jnp path."""
    tree = {
        "w": jnp.ones((16, 32)).at[3, 4].set(jnp.nan).at[0, 1].set(jnp.inf),
        "mu": jnp.ones((8, 8)).at[2, 2].set(jnp.nan),
        "step": jnp.zeros((), jnp.int32),
    }
    monkeypatch.setenv("REPRO_KERNEL_PLANS", "1")
    space = ApproxSpace(ApproxConfig(mode="memory", policy="zero"))
    plan = space.plan_for(tree, scope="tree")
    assert plan.placement == "kernel"
    out, stats = space.scrub(tree, stats_lib.zeros())

    monkeypatch.setenv("REPRO_KERNEL_PLANS", "0")
    ref_space = ApproxSpace(ApproxConfig(mode="memory", policy="zero"))
    assert ref_space.plan_for(tree, scope="tree").placement == "local"
    ref_out, ref_stats = ref_space.scrub(tree, stats_lib.zeros())

    for k in ("w", "mu"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(ref_out[k])
        )
    assert stats_lib.as_dict(stats) == stats_lib.as_dict(ref_stats)
    # per-rule ledgers agree too
    assert space.rule_stats() == ref_space.rule_stats()

    # neighbor_mean has no bit-identical kernel analogue -> jnp fallback
    monkeypatch.setenv("REPRO_KERNEL_PLANS", "1")
    nm = ApproxSpace(ApproxConfig(mode="memory", policy="neighbor_mean"))
    assert nm.plan_for(tree, scope="tree").placement == "local"
