"""Open-loop arrival schedules in wall-clock seconds, drawn from a traffic file.

One general generator reads every traffic mix (``bench/traffic/<mix>.json``):

    {"prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                "min": 32, "max": 2048},
     "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                "min": 8, "max": 512},
     "arrivals": {"process": "poisson", "rate_rps": 1.0},
     "lead_in_s": 20}

Every seed gets the same work.  The schedule has two segments, the
lead-in ``[0, lead_in_s)`` and the measured window
``[lead_in_s, lead_in_s + seconds)``; each holds ``round(rate * length)``
requests whose prompt lengths, output lengths and inter-arrival gaps are
fixed multisets (the distributions' quantiles at ``(i + 0.5) / n``), put in
an order drawn from ``ORDER_SEED`` and scaled to fill their segment.  ``--seed`` draws what the requests say (their token ids), as it
draws the weights: with the window as long as a request lives, the order
decides how many tokens fall inside it, so an order that moved with the
seed moved ``tokens_per_s`` by a fifth between seeds (PERF.md).  The same
seed gives a bit-equal schedule.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()
# the one order of lengths and gaps every seed of every mix gets
ORDER_SEED = 0


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request: due at ``due_s`` seconds after the schedule starts."""

    due_s: float
    prompt: np.ndarray          # int32 token ids
    max_new: int


def length_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified draws of a length distribution, clipped to
    ``[min, max]`` and rounded to whole tokens (ascending)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length range [{lo}, {hi}]")
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        x = np.exp(math.log(float(spec["median"])) + float(spec["sigma"]) * z)
    elif dist == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gap_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified inter-arrival gaps (seconds) of the process."""
    process = spec["process"]
    rate = float(spec["rate_rps"])
    if rate <= 0:
        raise ValueError(f"rate_rps must be > 0 ({rate})")
    u = (np.arange(n) + 0.5) / n
    if process == "poisson":
        return -np.log1p(-u) / rate
    if process == "uniform":
        return np.full(n, 1.0 / rate)
    raise ValueError(f"unknown arrival process {process!r}")


def n_requests(mix: Dict[str, Any], length_s: float) -> int:
    return max(1, int(round(float(mix["arrivals"]["rate_rps"]) * length_s)))


def _segment(mix, order, rng, start_s: float, length_s: float, vocab: int
             ) -> List[Arrival]:
    n = n_requests(mix, length_s)
    gaps = gap_quantiles(mix["arrivals"], n)[order.permutation(n)]
    prompts = length_quantiles(mix["prompt"], n)[order.permutation(n)]
    outputs = length_quantiles(mix["output"], n)[order.permutation(n)]
    due = start_s + length_s * (np.cumsum(gaps) - gaps) / gaps.sum()
    return [
        Arrival(
            due_s=float(due[i]),
            prompt=rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32),
            max_new=int(outputs[i]),
        )
        for i in range(n)
    ]


def schedule(mix: Dict[str, Any], seed: int, lead_in_s: float, seconds: float,
             vocab: int) -> List[Arrival]:
    """The arrivals of the lead-in and the window, sorted by due time.

    Draw order (fixed), per segment, lead-in first: from
    ``default_rng(ORDER_SEED)`` the gap, prompt-length and output-length
    permutations; from ``default_rng(seed)`` each request's token ids in
    arrival order."""
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    out = _segment(mix, order, rng, 0.0, lead_in_s, vocab) if lead_in_s > 0 else []
    return out + _segment(mix, order, rng, lead_in_s, seconds, vocab)
