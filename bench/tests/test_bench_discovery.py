"""A cell, a mix, a configuration and a metric added only as files are
found by name, with no edit to an existing file."""
import json
import types

import pytest

from bench.harness.registry import Registry
from bench.harness import runner

from bench.tests import tiny


def test_added_files_are_found(tmp_path):
    d = tiny.make(tmp_path)
    (d / "metrics" / "prompt_tokens_mean.py").write_text(
        "def read(run):\n"
        "    n = run.log.prompt_len\n"
        "    return sum(n.values()) / len(n) if n else None\n"
    )
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "prompt_tokens_mean", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p90_ms", "workloads": ["tiny.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = Registry(d)
    cell = runner.load_cell(reg, "tiny.mix")
    assert cell.config["system"]["name"] == "tiny"
    assert cell.traffic["arrivals"]["rate_rps"] == 4.0
    assert cell.shapes.d_model == 128
    names = [m["name"] for m in reg.metrics_for("tiny.mix", "per_layer")]
    assert "prompt_tokens_mean" in names
    assert "scrub.device_ms_per_step" not in names     # listed for other cells
    run = types.SimpleNamespace(log=types.SimpleNamespace(prompt_len={1: 4, 2: 8}))
    assert reg.metric("prompt_tokens_mean").read(run) == 6.0
    assert reg.reference("decoder_lm").logits_rows


def test_every_listed_piece_exists():
    reg = Registry()
    spec = reg.spec()
    for w in spec["workloads"]:
        cell = runner.load_cell(reg, w["name"])
        assert cell.workload["chips"] in (1, 4)
        reg.reference(cell.config["reference"])
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert callable(reg.metric(m["name"]).read)


def test_exact_memory_takes_no_flips(tmp_path):
    d = tiny.make(tmp_path)
    cfg = json.loads((d / "configs" / "tiny.json").read_text())
    (d / "configs" / "tiny.json").write_text(json.dumps({**cfg, "memory": "exact"}))
    reg = Registry(d)
    assert runner.load_cell(reg, "tiny.mix").ber == 0.0
    spec = json.loads((d / "cells" / "tiny.mix.json").read_text())
    (d / "cells" / "tiny.mix.json").write_text(json.dumps({**spec, "ber": 1e-9}))
    with pytest.raises(ValueError, match="exact memory"):
        runner.load_cell(reg, "tiny.mix")
