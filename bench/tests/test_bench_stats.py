"""Percentiles, censored time to first token and inter-token gaps."""
import statistics

import pytest

from bench.harness import stats


def test_percentile_interpolates_linearly():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 90) == pytest.approx(9.0)
    assert stats.percentile(list(range(101)), 99) == pytest.approx(99.0)
    assert stats.percentile([], 90) is None


def test_censored_ttft_counts_the_wait_so_far():
    due = {1: 1.0, 2: 2.0, 3: 9.0}
    first = {1: 1.5, 3: 12.0}                    # 2 never, 3 after the close
    got = stats.censored_ttft(due, first, window_end=10.0)
    assert got == pytest.approx([0.5, 8.0, 1.0])


def test_inter_token_gaps_in_window():
    times = [[0.0, 1.0, 3.0, 6.0], [2.5, 2.75]]
    assert stats.inter_token_gaps(times, 1.0, 5.0) == pytest.approx([1.0, 2.0, 0.25])
    assert stats.inter_token_gaps([[4.0]], 0.0, 9.0) == []


def test_spread_uses_python_quartiles():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / med)
