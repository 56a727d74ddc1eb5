"""The trace reducer: busy time, window, kernel sums, idle gaps, with the
injection passes cut out — on a hand-made record and on a small trace
recorded on a TPU v5e (``data/``)."""
import gzip
import json
import pathlib

import pytest

from bench.harness.trace import Trace

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000


def _record():
    # two steps of 10 ms with an injection pass of 5 ms between them
    host = [["bench.step", 0, 10 * MS], ["PjitFunction(paged_step)", 1 * MS, 2 * MS],
            ["bench.inject", 10 * MS, 5 * MS],
            ["bench.step", 15 * MS, 10 * MS], ["np.asarray", 22 * MS, 3 * MS]]
    ops = [["_paged_kernel", 2 * MS, 4 * MS], ["fusion.1", 5 * MS, 2 * MS],
           ["flip_bits", 11 * MS, 3 * MS],            # inside the injection
           ["_paged_kernel", 16 * MS, 5 * MS]]
    mods = [["jit_paged_step(1)", 2 * MS, 5 * MS], ["jit_paged_step(1)", 16 * MS, 5 * MS]]
    return {"device": {"ops": ops, "modules": mods}, "host": host}


def test_busy_and_window_leave_out_injection():
    t = Trace(_record())
    assert t.window_s == pytest.approx(0.020)
    # union of [2, 7) and [16, 21); the flip op is cut with its pass
    assert t.busy_s == pytest.approx(0.010)
    assert t.n_steps == 2


def test_sums_and_modules():
    t = Trace(_record())
    assert t.op_seconds(lambda n: "paged" in n) == pytest.approx(0.009)
    assert t.module_durations(lambda n: "paged_step" in n) == pytest.approx([0.005, 0.005])
    top = t.top_ops(2)
    assert top[0] == ["_paged_kernel", pytest.approx(0.009)]


def test_idle_gaps_labelled_by_host_span():
    gaps = Trace(_record()).idle_gaps(3)
    # [21, 25) in the second step while the host reads back; [7, 10) and
    # [0, 2) in the first
    assert gaps[0] == ["bench.step > np.asarray", pytest.approx(0.004)]
    assert gaps[1] == ["bench.step", pytest.approx(0.003)]
    assert gaps[2] == ["bench.step > PjitFunction(paged_step)", pytest.approx(0.002)]


def test_recorded_trace():
    paths = list(DATA.glob("*.json.gz"))
    assert paths
    for path in paths:
        rec = json.loads(gzip.decompress(path.read_bytes()))
        t = Trace(rec)
        want = rec["expected"]
        assert t.busy_s == pytest.approx(want["busy_s"])
        assert t.window_s == pytest.approx(want["window_s"])
        assert 0 < t.busy_s <= t.window_s
        for pattern, seconds in want["op_seconds"].items():
            assert t.op_seconds(lambda n: pattern in n) == pytest.approx(seconds)
        # the paged attention kernels take most of the device time; the
        # control-flow op that spans a step's layers is never listed as work
        kernels = want["op_seconds"]["paged_attention"] + want["op_seconds"]["paged_prefill"]
        assert kernels > 0.8 * t.busy_s
        assert want["op_seconds"]["paged_prefill"] > 0
        assert all(not name.startswith("while") for name, _ in t.top_ops(10))
