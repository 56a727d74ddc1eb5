"""Bring-up smoke test: serve qwen2-1.5b at full size through the Engine.

Runs the serving main path once on a TPU, through the entry points a user
calls (``build_model`` -> ``Engine.add_request`` / ``Engine.run``), at the
published qwen2-1.5b config (28 layers, d_model 1536, GQA 12/2, head_dim
128, d_ff 8960, vocab 151936, bf16) with random weights from ``--seed``.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # only the sharded four-chip phase

One chip:
  1. kernels  — the fused paged decode, split-K decode and chunked prefill
                kernels, compiled (``tpu_custom_call`` in the HLO), match
                ``kernels/ref.py`` at the model's attention widths on a
                pool with injected NaN/Inf lanes;
  2. serve    — 8 requests (prompts of 128-700 tokens, 32 new tokens each)
                at BER 0 and at BER 1e-6 over a 1024-row page pool, on the
                fused path: paged decode + split-K + chunked prefill kernels
                compiled, the reactive page scrub on the Pallas kernel, the
                engine's default pool rule (NaN/Inf plus its range guard),
                no non-finite readout;
  3. parity   — at BER 0 each request's first generated token equals the
                gathered jnp path's (``launch.serve.generate(paged=False)``).

Four chips: a ("data", "model") = (4, 1) mesh, the same requests through
an ``Engine`` whose space carries the mesh (the engine's default sharding
rules: pool pages and weights over "data"; the device-local shard_map
walk, with pages handed out round-robin over the four pool shards),
against a one-device engine in the same process.  Both record
their readout logits; on every context the two shared (each request up to
and including its first differing token) the sharded engine's logits must
sit within ``SHARD_TOL`` times as far from the one-device engine's as those
sit from the gathered jnp path, teacher-forced on the same contexts.

Every figure printed before the last line is a set-up/smoke figure of this
run, not a benchmark number.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ARCH = "qwen2-1.5b"
# ServingConfig of the smoke: 1023 pages + the null page = 1024 pool rows
# (~0.47 GB of bf16 KV), a block table 64 pages wide (split-K engages)
GEOMETRY = dict(
    page_size=16, n_pages=1023, max_batch=8, max_pages_per_request=64,
    prefill_chunk=256, repair="page",
)
N_REQUESTS = 8
PROMPT_LEN = (128, 700)
MAX_NEW = 32
BER = 1e-6
# --chips 4: on the contexts both engines shared, the sharded engine's
# logits may sit at most this many times further from the one-device
# engine's than those sit from the gathered jnp path.  Both gaps are
# rounding: the default sharding rules reorder every weight contraction
# (embed over "data"), where the gathered path differs in attention alone,
# and a CPU f32 rehearsal (tests/multidev) measured ratios of 1.4-2.1.  A
# misrouted page or a broken merge moves logits by O(max|logit|) instead.
SHARD_TOL = 4.0
# bf16 outputs of the kernels against the f32-softmax oracle
KERNEL_TOL = dict(atol=3e-2, rtol=3e-2)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu():
    """The TPU devices JAX sees; exits non-zero on any other backend."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}"
        )
    return devices


def import_repo() -> None:
    """Make ``src/`` importable (the script runs from a plain checkout)."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401 — fails here when run outside the repository


def model_config(arch: str = ARCH):
    """The published config; the engine's space owns repair, so the
    model's own read-site repair is off (as in examples/serve_engine.py)."""
    from repro.configs import get_config
    from repro.runtime import ApproxConfig

    return dataclasses.replace(
        get_config(arch), repair=ApproxConfig(mode="off")
    )


def make_prompts(seed: int, vocab: int, n: int, lo: int, hi: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = rng.integers(lo, hi + 1, size=n)
    return [rng.integers(0, vocab, size=int(n_tok)).tolist() for n_tok in lengths]


# --------------------------------------------------------------------------
# Phase 1: the paged kernels against kernels/ref.py
# --------------------------------------------------------------------------
def _run_compiled(fn, *args, interpret: bool):
    """Compile ``fn`` once, check the kernel is in it (not interpreted),
    and run that executable."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if not interpret:
        assert "tpu_custom_call" in compiled.as_text(), "kernel not compiled"
    return compiled(*args)


def check_kernels(
    seed: int, *, heads: int, kv_heads: int, head_dim: int, page_size: int,
    batch: int, width: int, rows: int, chunk: int, dtype,
    interpret: bool = False,
) -> dict:
    """Decode (serial and split-K) and chunked prefill at the serving
    widths, on a pool with NaN/Inf lanes parked in pages the requests read
    (and one in the null page, which no context reads): outputs finite and
    within bf16 tolerance of the oracle, per-slot fatal counts identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_attention as pa
    from repro.kernels import ref
    from repro.serving.config import ServingConfig

    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    layers, layer = 2, 1                # a non-zero pool row: layer indexing
    shape = (rows, layers, page_size, kv_heads, head_dim)
    kp = jax.random.normal(keys[0], shape, jnp.float32).astype(dtype)
    vp = jax.random.normal(keys[1], shape, jnp.float32).astype(dtype)
    null = rows - 1

    def tables(n_req, last_pos):
        bt = np.full((n_req, width), null, np.int32)
        perm = rng.permutation(null)
        off = 0
        for b in range(n_req):
            used = int(last_pos[b]) // page_size + 1
            bt[b, :used] = perm[off:off + used]
            off += used
        return bt

    pos = rng.integers(page_size, width * page_size, size=batch).astype(np.int32)
    bt = tables(batch, pos)
    # fatal lanes inside read positions of request 0 and 1, one in the null
    # page (padding: never counted)
    last = head_dim - 1
    faults = [
        ("k", bt[0, 0], 3, 0, 5, jnp.nan), ("k", bt[1, 0], 0, 1, last, jnp.nan),
        ("v", bt[0, 0], 2, 1, 1, jnp.inf), ("v", bt[1, 0], 1, 0, last, jnp.nan),
        ("k", null, 0, 0, 0, jnp.nan),
    ]
    for name, page, slot, head, d, val in faults:
        if name == "k":
            kp = kp.at[page, layer, slot, head, d].set(val)
        else:
            vp = vp.at[page, layer, slot, head, d].set(val)
    q = jax.random.normal(keys[2], (batch, heads, head_dim), jnp.float32).astype(dtype)
    lay = jnp.int32(layer)
    splits = ServingConfig(
        page_size=page_size, n_pages=rows - 1, max_batch=batch,
        max_pages_per_request=width,
    ).resolve_split_k()
    out = {}

    def decode(q, kp, vp, bt, pos, lay):
        return pa.paged_attention_raw(q, kp, vp, bt, pos, lay, interpret=interpret)

    def splitk(q, kp, vp, bt, pos, lay):
        return pa.paged_attention_splitk_raw(
            q, kp, vp, bt, pos, lay, splits=splits, interpret=interpret
        )

    with jax.default_matmul_precision("highest"):
        want, want_slots = ref.paged_attention_ref(q, kp, vp, bt, pos, layer=layer)
    for name, fn in (("paged_decode", decode), ("paged_decode_splitk", splitk)):
        got, slots, counts = _run_compiled(
            fn, q, kp, vp, bt, pos, lay, interpret=interpret
        )
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all(), f"{name}: non-finite output"
        err = float(np.max(np.abs(got - np.asarray(want, np.float32))))
        np.testing.assert_allclose(got, np.asarray(want, np.float32), **KERNEL_TOL)
        np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_slots))
        assert int(counts[pa.EV_TOTAL]) > 0, f"{name}: injected faults not seen"
        out[name] = {"max_abs_err": err, "fatal_lanes": int(np.asarray(slots).sum())}

    n_pre = 2
    q_start = rng.integers(0, width * page_size - chunk + 1, size=n_pre).astype(np.int32)
    bt_pre = tables(n_pre, q_start + chunk - 1)
    bt_pre[:, 0] = bt[:n_pre, 0]        # the first pages hold fatal lanes
    qc = jax.random.normal(
        keys[3], (n_pre, chunk, heads, head_dim), jnp.float32
    ).astype(dtype)

    def prefill(qc, kp, vp, bt, qs, lay):
        return pa.paged_prefill_raw(qc, kp, vp, bt, qs, lay, interpret=interpret)

    got, slots, counts = _run_compiled(
        prefill, qc, kp, vp, bt_pre, q_start, lay, interpret=interpret
    )
    with jax.default_matmul_precision("highest"):
        want, want_slots = ref.paged_prefill_ref(
            qc, kp, vp, bt_pre, q_start, layer=layer
        )
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all(), "paged_prefill: non-finite output"
    err = float(np.max(np.abs(got - np.asarray(want, np.float32))))
    np.testing.assert_allclose(got, np.asarray(want, np.float32), **KERNEL_TOL)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_slots))
    assert int(counts[pa.EV_TOTAL]) > 0, "paged_prefill: injected faults not seen"
    out["paged_prefill"] = {
        "max_abs_err": err, "fatal_lanes": int(np.asarray(slots).sum()),
    }
    return out


# --------------------------------------------------------------------------
# Phase 2: serve through the Engine
# --------------------------------------------------------------------------
def assert_fused_path(
    engine, *, interpret: bool = False, scrub_placement: str = "kernel"
) -> None:
    """The run must exercise the real path: the fused paged decode and
    chunked prefill (never the gathered fallback), split-K, kernels
    compiled rather than interpreted, and the page scrub where expected —
    the Pallas kernel on one device; GSPMD shard-local over a sharded pool
    (the page gather has no shard_map entry)."""
    import jax.numpy as jnp

    from repro.kernels import common

    assert engine.paged_plan is not None, "fused paged decode not engaged"
    assert engine._prefill_fn is not None, "fused paged prefill not engaged"
    assert engine._split_k > 1, "split-K decode not engaged"
    assert common.default_interpret() is interpret, "kernel interpret mode"
    plan = engine.space.plan_for(
        engine.pool.tree, scope="pages", trigger="reactive"
    )
    assert plan.placement == scrub_placement, (
        f"page scrub placement {plan.placement!r}"
    )
    if interpret:
        return
    cfg = engine.cfg
    B, M = cfg.max_batch, cfg.max_pages_per_request
    null = engine.pool.null_page
    decode_hlo = engine._paged_fn.lower(
        engine.params, engine.pool.tree,
        {"tokens": jnp.zeros((B, 1), jnp.int32)},
        jnp.full((B, M), null, jnp.int32), jnp.zeros((B,), jnp.int32),
        engine._stream,
    ).as_text()
    prefill_hlo = engine._prefill_fn.lower(
        engine.params, engine.pool.tree,
        {"tokens": jnp.zeros((1, cfg.prefill_chunk), jnp.int32)},
        jnp.full((1, M), null, jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32), engine._stream,
    ).as_text()
    assert "tpu_custom_call" in decode_hlo, "decode step holds no kernel"
    assert "tpu_custom_call" in prefill_hlo, "prefill step holds no kernel"


def interleave_pages(engine, shards: int) -> None:
    """Hand the pool's pages out round-robin over ``shards`` equal row
    blocks, through its own alloc/free, so a run's requests hold pages in
    every shard of a sharded pool and not only in the first (the free list
    starts in row order)."""
    pool = engine.pool
    rows = (engine.cfg.n_pages + 1) // shards
    pages = pool.alloc(pool.n_free)
    pool.free(sorted(pages, key=lambda p: (p % rows, p // rows)))


def serve(model, params, prompts, *, ber: float, seed: int, geometry: dict,
          max_new: int, space=None, interpret: bool = False,
          scrub_placement: str = "kernel", record_logits: bool = False,
          page_shards: int = 1) -> dict:
    """One Engine run over ``prompts``; returns its tokens and counters
    (and each generated token's readout logits with ``record_logits``).
    ``page_shards`` > 1 interleaves the pool's pages over that many row
    blocks (``interleave_pages``)."""
    import jax

    from repro.serving import Engine, ServingConfig

    engine = Engine(
        model, params,
        ServingConfig(**geometry, ber=ber, seed=seed, record_logits=record_logits),
        space=space,
    )
    assert_fused_path(
        engine, interpret=interpret, scrub_placement=scrub_placement
    )
    if page_shards > 1:
        interleave_pages(engine, page_shards)
    rids = [engine.add_request(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    engine.step()                   # compile-dominated
    t1 = time.perf_counter()
    results = engine.run() if engine.has_work else engine.results
    jax.block_until_ready(engine.pool.tree)
    t2 = time.perf_counter()
    metrics, stats = engine.metrics(), engine.stats_dict()
    vocab = model.cfg.vocab
    for rid in rids:
        res = results.get(rid)
        assert res is not None, f"request {rid} never finished"
        gen = res["generated"]
        assert len(gen) == max_new and not res["truncated"], (rid, len(gen))
        assert all(0 <= t < vocab for t in gen), f"request {rid}: token out of range"
    out = {
        "generated": [results[r]["generated"] for r in rids],
        "first_step_s": t1 - t0,
        "rest_s": t2 - t1,
        "steps": engine._t,
        "metrics": metrics,
        "stats": stats,
    }
    if record_logits:
        out["logits"] = [results[r]["logits"] for r in rids]
    del engine
    return out


def report_serve(tag: str, r: dict) -> None:
    m, s = r["metrics"], r["stats"]
    log(
        f"{tag}: {len(r['generated'])} requests, {m['tokens_emitted']} tokens "
        f"in {r['steps']} engine steps; first step {r['first_step_s']:.3f} s "
        f"(includes compile), remaining steps {r['rest_s']:.3f} s "
        "(smoke wall time, not a benchmark)"
    )
    log(
        f"{tag}: flips={s['flips']} nan_found={s['nan_found']} "
        f"inf_found={s['inf_found']} events={s['events']} "
        f"scrub_calls={m['scrub_calls']} paged_kernel_events="
        f"{m['paged_kernel_events']} nonfinite_logit_rows="
        f"{m['nonfinite_logit_rows']} split_k={m['split_k']} "
        f"pool_gathers={m['pool_gathers']} host_syncs={m['n_host_syncs']}"
    )


def gathered_logits_fn(model, max_seq: int, n: int):
    """Teacher-forced logits of the gathered jnp path (contiguous cache, no
    Pallas kernel): ``fn(params, prompt, generated)`` returns the (n, vocab)
    f32 readout rows that predict ``generated[:n]`` from ``prompt +
    generated[:i]``.  Contexts are zero-padded to ``max_seq`` (causal
    attention: the padding never reaches the rows read), so the prefill
    compiles once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cache = model.init_cache(1, max_seq)

    @jax.jit
    def rows(params, tokens, start):
        logits, _ = model.prefill(
            params, cache, {"tokens": tokens}, jnp.zeros((), jnp.int32)
        )
        return jax.lax.dynamic_slice_in_dim(logits[0], start, n).astype(jnp.float32)

    def fn(params, prompt, generated):
        context = list(prompt) + list(generated[:n - 1])
        tokens = np.zeros((1, max_seq), np.int32)
        tokens[0, :len(context)] = context
        return np.asarray(rows(params, jnp.asarray(tokens), len(prompt) - 1))

    return fn


def check_first_tokens(model, params, prompts, served: dict, max_seq: int) -> int:
    """Each request's first generated token must equal the gathered jnp
    path's (``generate(paged=False)``).  A mismatch is logged with both
    tokens' logits on that path before the assertion fails."""
    import jax.numpy as jnp

    from repro.launch.serve import generate

    mismatches = []
    for i, prompt in enumerate(prompts):
        toks, _ = generate(
            model, params, jnp.asarray([prompt], jnp.int32),
            max_new=1, max_seq=max_seq, paged=False,
        )
        want, got = int(toks[0, len(prompt)]), served["generated"][i][0]
        if got != want:
            mismatches.append((i, got, want))
    if mismatches:
        ref = gathered_logits_fn(model, max_seq, 1)
        for i, got, want in mismatches:
            row = ref(params, prompts[i], [got])[0]
            log(f"request {i}: engine token {got} (logit {row[got]}) vs "
                f"gathered {want} (logit {row[want]}), max|logit| "
                f"{abs(row).max()}")
    assert not mismatches, f"first tokens differ: {mismatches}"
    return len(prompts)


def compare_sharded(model, params, prompts, *, mesh, seed: int, geometry: dict,
                    max_new: int, interpret: bool = False) -> dict:
    """The requests through a one-device engine and through an engine whose
    space carries ``mesh`` (``engine_space(model, mesh=mesh)``: the
    engine's default sharding rules), both recording readout logits, both
    handing out pages round-robin over the mesh's "data" shards (so every
    device's partials carry real pages into the sharded merge).  On every
    context the two engines shared — each request up to and
    including its first differing token — ``d_shard`` is the largest logit
    difference between them and ``d_ref`` the largest between the
    one-device engine and the gathered jnp path teacher-forced on the same
    contexts."""
    import numpy as np

    from repro.serving import engine_space

    kw = dict(ber=0.0, seed=seed, geometry=geometry, max_new=max_new,
              interpret=interpret, record_logits=True,
              page_shards=mesh.shape["data"])
    single = serve(model, params, prompts, **kw)
    sharded = serve(model, params, prompts, space=engine_space(model, mesh=mesh),
                    scrub_placement="sharded", **kw)
    max_seq = geometry["page_size"] * geometry["max_pages_per_request"]
    ref = gathered_logits_fn(model, max_seq, max_new)
    d_shard = d_ref = 0.0
    shared, first_diff = [], []
    for prompt, a, b, la, lb in zip(
        prompts, single["generated"], sharded["generated"],
        single["logits"], sharded["logits"],
    ):
        k = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        first_diff.append(k)
        n = len(a) if k is None else k + 1
        shared.append(n)
        d_shard = max(d_shard, float(np.abs(la[:n] - lb[:n]).max()))
        d_ref = max(d_ref, float(np.abs(la[:n] - ref(params, prompt, a)[:n]).max()))
    return {
        "single": single, "sharded": sharded,
        "sharded_kernels": sharded["metrics"]["sharded_kernels"],
        "identical": sum(k is None for k in first_diff),
        "first_diff": first_diff, "shared_positions": shared,
        "d_shard": d_shard, "d_ref": d_ref,
    }


def one_chip(args, device) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    t0 = time.perf_counter()
    kern = check_kernels(
        args.seed, heads=12, kv_heads=2, head_dim=128, page_size=16,
        batch=GEOMETRY["max_batch"], width=GEOMETRY["max_pages_per_request"],
        rows=GEOMETRY["n_pages"] + 1, chunk=GEOMETRY["prefill_chunk"],
        dtype=jnp.bfloat16,
    )
    log(f"kernels vs kernels/ref.py: {kern} ({time.perf_counter() - t0:.1f} s "
        "incl. compile)")

    cfg = model_config()
    model = build_model(cfg)
    t0 = time.perf_counter()
    # one compiled program instead of an eager dispatch per leaf
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model {cfg.name}: {n_params} params ({cfg.dtype}), init "
        f"{time.perf_counter() - t0:.1f} s on {device.device_kind}")
    prompts = make_prompts(args.seed, cfg.vocab, N_REQUESTS, *PROMPT_LEN)
    log(f"prompt lengths {[len(p) for p in prompts]}, max_new {MAX_NEW}")

    clean = serve(model, params, prompts, ber=0.0, seed=args.seed,
                  geometry=GEOMETRY, max_new=MAX_NEW)
    report_serve("ber=0", clean)
    # the engine's default space: NaN/Inf detection plus its range guard
    faulty = serve(model, params, prompts, ber=BER, seed=args.seed,
                   geometry=GEOMETRY, max_new=MAX_NEW)
    report_serve(f"ber={BER:g}", faulty)
    s = faulty["stats"]
    assert s["flips"] > 0, "no bit flips injected"
    assert s["nan_found"] + s["inf_found"] > 0, "no fatal lane detected"
    for tag, run in (("ber=0", clean), (f"ber={BER:g}", faulty)):
        assert run["metrics"]["nonfinite_logit_rows"] == 0, (
            f"{tag}: non-finite logits reached the readout"
        )

    t0 = time.perf_counter()
    max_seq = GEOMETRY["page_size"] * GEOMETRY["max_pages_per_request"]
    n = check_first_tokens(model, params, prompts, clean, max_seq)
    log(f"first tokens vs gathered jnp path: {n}/{len(prompts)} equal "
        f"({time.perf_counter() - t0:.1f} s incl. compile)")


def four_chips(args, devices) -> None:
    """A (4, 1) mesh engine against a one-device engine (``compare_sharded``):
    the sharded walk must engage, and on the contexts both engines shared
    the sharded logits must sit within ``SHARD_TOL`` times the one-device
    engine's own distance from the gathered jnp path.  Token streams are reported, not
    required equal: bf16 logits of random weights are flat, so a reordered
    reduction alone flips a greedy token at a near-tie."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.models import build_model

    assert len(devices) >= 4, f"--chips 4 needs four devices, found {len(devices)}"
    mesh = make_mesh((4, 1), ("data", "model"), devices=devices[:4])
    cfg = model_config()
    model = build_model(cfg)
    # one compiled program instead of an eager dispatch per leaf
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    prompts = make_prompts(args.seed, cfg.vocab, N_REQUESTS, *PROMPT_LEN)
    t0 = time.perf_counter()
    cmp = compare_sharded(model, params, prompts, mesh=mesh, seed=args.seed,
                          geometry=GEOMETRY, max_new=MAX_NEW)
    report_serve("1 chip", cmp["single"])
    report_serve("4 chips", cmp["sharded"])
    log(f"4 chips: sharded_kernels={cmp['sharded_kernels']}, identical token "
        f"streams {cmp['identical']}/{len(prompts)}, first differing index "
        f"{cmp['first_diff']}, shared contexts {cmp['shared_positions']}; "
        f"max |logit difference| on them: sharded vs 1 chip {cmp['d_shard']}, "
        f"1 chip vs gathered jnp path {cmp['d_ref']} "
        f"({time.perf_counter() - t0:.1f} s incl. compile)")
    assert cmp["sharded_kernels"], "sharded walk not engaged"
    assert cmp["d_shard"] <= SHARD_TOL * cmp["d_ref"], (
        f"sharded logits {cmp['d_shard']} from the one-device engine, over "
        f"{SHARD_TOL:g}x its own {cmp['d_ref']} from the gathered jnp path"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu()
    import_repo()
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.device_kind}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, devices)
    else:
        one_chip(args, dev)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
