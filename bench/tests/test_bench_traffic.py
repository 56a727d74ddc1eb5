"""The wall-clock traffic generator: bit-equal for a seed, the same work
for every seed, lengths clipped, distributions as the file says."""
import math

import numpy as np
import pytest

from bench.harness import traffic

MIX = {
    "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32, "max": 2048},
    "output": {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 8, "max": 512},
    "arrivals": {"process": "poisson", "rate_rps": 2.0},
    "lead_in_s": 5,
}


def _flat(arrivals):
    return [(a.due_s, a.prompt.tolist(), a.max_new) for a in arrivals]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_same_seed_same_schedule(seed):
    a = traffic.schedule(MIX, seed, 5.0, 55.0, 151936)
    b = traffic.schedule(MIX, seed, 5.0, 55.0, 151936)
    assert _flat(a) == _flat(b)


def test_seeds_share_the_work_and_differ_in_content():
    a = traffic.schedule(MIX, 1, 5.0, 55.0, 1000)
    b = traffic.schedule(MIX, 2, 5.0, 55.0, 1000)
    assert len(a) == len(b) == 10 + 110
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] == [
        (x.due_s, len(x.prompt), x.max_new) for x in b]
    assert _flat(a) != _flat(b)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    assert a[0].due_s == 0.0 and a[10].due_s == 5.0
    # the window holds its own stratified set, whatever the lead-in
    w = sorted(len(x.prompt) for x in a if x.due_s >= 5.0)
    assert w == sorted(traffic.length_quantiles(MIX["prompt"], 110).tolist())


def test_lengths_clipped_and_distributed():
    n = 2001
    q = traffic.length_quantiles(MIX["prompt"], n)
    assert q.min() >= 32 and q.max() <= 2048
    assert q[n // 2] == 512                      # the median draw
    # clipping: the lognormal's tails pile up at the ends
    assert (q == 32).sum() > 0 and (q == 2048).sum() > 0
    u = (np.arange(n) + 0.5) / n
    # the quartiles of a lognormal with sigma 0.9
    assert abs(q[int(0.25 * n)] - 512 * math.exp(-0.6745 * 0.9)) <= 1
    assert abs(q[int(0.75 * n)] - 512 * math.exp(0.6745 * 0.9)) <= 2
    assert len(u) == n


def test_poisson_gaps_have_the_rate():
    g = traffic.gap_quantiles(MIX["arrivals"], 10000)
    assert abs(g.mean() - 0.5) < 0.01           # 1 / rate
    assert abs(np.median(g) - math.log(2) / 2.0) < 0.001


def test_tokens_drawn_in_range():
    for a in traffic.schedule(MIX, 3, 0.0, 10.0, 97):
        assert a.prompt.dtype == np.int32
        assert a.prompt.min() >= 0 and a.prompt.max() < 97


def test_unknown_distribution_refused():
    with pytest.raises(ValueError):
        traffic.length_quantiles({"dist": "zipf", "min": 1, "max": 2}, 3)
    with pytest.raises(ValueError):
        traffic.gap_quantiles({"process": "bursty", "rate_rps": 1.0}, 3)
