"""A tiny configuration, mix and cell, added as files beside a copy of the
benchmark in a temporary directory — how the tests drive the harness on the
CPU (Pallas in interpret mode, float32) without touching the real cells."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

CONFIG = {
    "hidden_act": "silu", "hidden_size": 128, "initializer_range": 0.02,
    "intermediate_size": 256, "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "vocab_size": 512,
    "source": "tests only", "reduced": [], "reference": "decoder_lm",
    "layout": {"norm": "rms", "norm_eps": 1e-6, "mlp": "swiglu", "qkv_bias": True,
               "o_bias": False, "mlp_bias": False, "tied": True},
    "system": {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 128,
               "n_heads": 4, "n_kv": 2, "d_ff": 256, "vocab": 512, "head_dim": 32,
               "qkv_bias": True, "rope_theta": 10000.0, "norm": "rms",
               "mlp": "swiglu", "tie_embeddings": True, "dtype_name": "float32"},
}
MIX = {
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 28},
    "output": {"dist": "lognormal", "median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "arrivals": {"process": "poisson", "rate_rps": 4.0},
    "lead_in_s": 0.0,
}
CELL = {
    "config": "tiny", "traffic": "tiny-mix", "ber": 0.0, "inject_every": 1,
    "engine": {"page_size": 8, "n_pages": 16, "max_batch": 4,
               "max_pages_per_request": 4, "prefill_chunk": 16, "repair": "page"},
    # float32 served against the float32 reference: rounding only
    "correct": {"limit": 1e-3, "sample_tokens": 16,
                "min_requests": 2, "max_requests": 4},
}


def make(tmp: pathlib.Path, cell: dict = None) -> pathlib.Path:
    """A copy of the benchmark under ``tmp/bench`` with the tiny cell
    ``tiny.mix`` added as files; returns the copy's directory."""
    dst = tmp / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (dst / "traffic" / "tiny-mix.json").write_text(json.dumps(MIX))
    (dst / "cells" / "tiny.mix.json").write_text(json.dumps(cell or CELL))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "tests only",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "tests"})
    spec["workloads"].append({"name": "tiny.mix", "config": "tiny",
                              "traffic": "tiny-mix", "chips": 1, "why": "tests"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst
