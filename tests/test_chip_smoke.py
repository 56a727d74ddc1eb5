"""``chip_smoke.py`` on the CPU: its phases at a tiny size with the kernels
in interpret mode, so the script cannot rot between chip runs, and its
refusal to run anywhere but on a TPU."""
import dataclasses
import importlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

GEOMETRY = dict(
    page_size=4, n_pages=31, max_batch=2, max_pages_per_request=8,
    prefill_chunk=8, repair="page",
)


@pytest.fixture(scope="module")
def cs():
    sys.path.insert(0, str(REPO))
    try:
        yield importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(str(REPO))


@pytest.fixture(scope="module")
def tiny(cs):
    from repro.models import build_model

    cfg = dataclasses.replace(
        cs.model_config().reduced(),
        n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16,
        d_ff=128, vocab=97,
    )
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def test_refuses_to_run_off_tpu(cs, capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_matches_ref(cs):
    out = cs.check_kernels(
        0, heads=4, kv_heads=2, head_dim=16, page_size=4, batch=2,
        width=8, rows=32, chunk=8, dtype=jnp.float32, interpret=True,
    )
    assert set(out) == {"paged_decode", "paged_decode_splitk", "paged_prefill"}
    assert all(v["fatal_lanes"] > 0 for v in out.values())


def test_serve_phases(cs, tiny, monkeypatch):
    """BER 0 on the fused path with first tokens equal to the gathered
    path; under injection the engine's default space (NaN/Inf plus its
    range guard) keeps every readout finite, and the recorded logits agree
    with the gathered path teacher-forced on the engine's own stream."""
    monkeypatch.setenv("REPRO_KERNEL_PLANS", "1")    # kernel page scrub on CPU
    model, params = tiny
    prompts = cs.make_prompts(0, model.cfg.vocab, 3, 5, 20)
    kw = dict(seed=0, geometry=GEOMETRY, max_new=4, interpret=True)
    clean = cs.serve(model, params, prompts, ber=0.0, record_logits=True, **kw)
    assert clean["metrics"]["nonfinite_logit_rows"] == 0
    assert cs.check_first_tokens(model, params, prompts, clean, 32) == 3
    ref = cs.gathered_logits_fn(model, 32, 4)
    for prompt, gen, rows in zip(prompts, clean["generated"], clean["logits"]):
        assert rows.shape == (4, model.cfg.vocab)
        np.testing.assert_allclose(rows, ref(params, prompt, gen), atol=1e-4)
    faulty = cs.serve(model, params, prompts, ber=1e-3, **kw)
    assert faulty["stats"]["flips"] > 0
    assert faulty["metrics"]["nonfinite_logit_rows"] == 0
