"""The readers of the engine's own spans and program names: the interval
arithmetic on a hand-made record (idle inside and outside readbacks, the
injection cut, the three idle parts summing to the device's idle time),
and every new reader on a trace recorded on a TPU v5e (``data/``)."""
import gzip
import json
import pathlib
import types

import pytest

from bench.harness import spans
from bench.harness.registry import Registry
from bench.harness.trace import Trace

DATA = pathlib.Path(__file__).parent / "data"
RECORDED = DATA / "qwen2-1.5b.chat.approx.spans.trace.json.gz"
MS = 1_000_000
NEW = ("readback.idle_ms_per_step", "engine_host.idle_ms_per_step",
       "prefill_calls_per_step", "repair.device_ms_per_step",
       "page_reset.device_ms_per_step")


def _record():
    # two harness steps of 20 ms with an injection pass of 5 ms between
    # them; each holds an engine step that ends in a readback
    host = [["bench.step", 0, 20 * MS], ["engine.step", 1 * MS, 18 * MS],
            ["engine.prefill_chunk", 2 * MS, 2 * MS],
            ["engine.prefill_chunk", 5 * MS, 2 * MS],
            ["pool.reset_pages", 3 * MS, 1 * MS],
            ["engine.readback", 15 * MS, 3 * MS],
            ["bench.inject", 20 * MS, 5 * MS],
            ["engine.readback", 21 * MS, 2 * MS],       # inside the cut
            ["bench.step", 25 * MS, 20 * MS], ["engine.step", 26 * MS, 18 * MS],
            ["engine.readback", 40 * MS, 3 * MS]]
    ops = [["fusion.1", 3 * MS, 11 * MS],
           ["flip_bits", 21 * MS, 3 * MS],            # inside the cut
           ["_paged_kernel", 27 * MS, 12 * MS]]
    mods = [["jit_pool_reset_pages(3)", 3 * MS, 1 * MS],
            ["jit_repair_pages(1)", 8 * MS, 2 * MS],
            ["jit_inject(2)", 12 * MS, 1 * MS],        # by name, not the cut
            ["jit_repair_pages(1)", 21 * MS, 1 * MS],  # inside the cut
            ["jit_inject(2)", 22 * MS, 2 * MS]]
    return {"device": {"ops": ops, "modules": mods}, "host": host}


def _read(name, trace, ber=1e-9):
    run = types.SimpleNamespace(trace=trace, cell=types.SimpleNamespace(ber=ber))
    return Registry().metric(name).read(run)


def test_subtract():
    assert spans.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [
        (0, 2), (4, 8), (22, 29)]
    assert spans.subtract([(0, 10)], []) == [(0, 10)]
    assert spans.subtract([(0, 10)], [(0, 10)]) == []


def test_idle_split_and_injection_cut():
    t = Trace(_record())
    # window [0, 20) + [25, 45); busy [3, 14) + [27, 39)
    assert t.window_s == pytest.approx(0.040)
    assert t.busy_s == pytest.approx(0.023)
    split = spans.idle_split(t)
    # the readbacks [15, 18) and [40, 43) are idle; the one in the cut is gone
    assert split["readback"] == 6 * MS
    # [1, 3), [14, 15), [18, 19), [26, 27), [39, 40), [43, 44)
    assert split["engine_host"] == 7 * MS
    # [0, 1), [19, 20), [25, 26), [44, 45)
    assert split["outside"] == 4 * MS
    idle_pct = _read("device_idle_pct", t)
    assert sum(split.values()) == pytest.approx(idle_pct / 100 * t.window_ns)


def test_readers_on_a_made_trace():
    t = Trace(_record())
    assert _read("readback.idle_ms_per_step", t) == pytest.approx(3.0)
    assert _read("engine_host.idle_ms_per_step", t) == pytest.approx(3.5)
    # two chunks in the one step that holds any
    assert _read("prefill_calls_per_step", t) == pytest.approx(2.0)
    # the scrub in the window, not the injection beside it or the one cut
    assert _read("repair.device_ms_per_step", t) == pytest.approx(1.0)
    assert _read("repair.device_ms_per_step", t, ber=0.0) is None
    assert _read("page_reset.device_ms_per_step", t) == pytest.approx(0.5)


def test_readers_say_nothing_without_the_spans():
    # a program older than the engine spans and the stable program names
    rec = _record()
    rec["host"] = [e for e in rec["host"] if e[0].startswith("bench.")]
    rec["device"]["modules"] = [["jit_fn(1)", 8 * MS, 2 * MS],
                                ["jit__reset_pages(3)", 3 * MS, 1 * MS]]
    t = Trace(rec)
    assert spans.idle_split(t) is None
    for name in NEW:
        assert _read(name, t) is None
    assert _read("readback.idle_ms_per_step", None) is None


def test_recorded_trace_with_engine_spans():
    rec = json.loads(gzip.decompress(RECORDED.read_bytes()))
    t = Trace(rec)
    want = rec["expected"]
    for name in NEW:
        assert _read(name, t) == pytest.approx(want[name]), name
    assert not any(n.startswith("jit_fn(") for n, _, _ in t.modules)
    assert any(n.startswith("jit_pool_reset_pages(") for n, _, _ in t.modules)
    split = spans.idle_split(t)
    assert sum(split.values()) == pytest.approx(t.window_ns - t.busy_ns)
    assert split["readback"] > 0
    # every readback and prompt chunk lies inside an engine step
    steps = spans.events(t, spans.STEP)
    assert len(steps) == t.n_steps
    for name in (spans.READBACK, "engine.prefill_chunk"):
        for a, b in spans.events(t, name):
            assert any(s <= a and b <= e for s, e in steps), name
