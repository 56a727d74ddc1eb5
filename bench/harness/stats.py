"""Percentiles, censored time to first token, and the spread of a set of runs.

Percentiles interpolate linearly between order statistics (numpy's
default ``"linear"`` method): the p-th percentile of ``n`` samples sits at
rank ``p/100 * (n - 1)``.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The p-th percentile (linear interpolation), or None with no sample."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


def censored_ttft(due: Dict[int, float], first: Dict[int, float],
                  window_end: float) -> List[float]:
    """Time to first token of every request in ``due`` (rid -> due time),
    measured from its due time.  A request with no first token by
    ``window_end`` counts with the wait it has so far, so a stall cannot
    drop samples; a first token after the close is cut to the close."""
    out = []
    for rid, t_due in due.items():
        t = first.get(rid)
        t = window_end if t is None or t > window_end else t
        out.append(t - t_due)
    return out


def inter_token_gaps(token_times: Iterable[Sequence[float]], start: float,
                     end: float) -> List[float]:
    """Gaps between consecutive output tokens of each request whose later
    token lands in ``[start, end)``."""
    gaps = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if start <= b < end:
                gaps.append(b - a)
    return gaps


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (Python's default quartiles:
    ``statistics.quantiles(values, n=4)``, the ``"exclusive"`` method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
