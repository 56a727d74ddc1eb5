"""Interval arithmetic over the engine's own profiler spans, for the
readers that split a trace's time by engine stage.

The engine wraps each step in an ``engine.step`` span and each blocking
device-to-host read in an ``engine.readback`` span (``serving/engine.py``),
on the same host thread as the harness's ``bench.*`` spans, so
``trace.extract`` keeps them.  Every interval here is clipped to the
trace's kept window (the ``bench.inject`` passes cut out), so lengths add
up with ``Trace.window_ns`` and ``Trace.busy_ns``.  A program without those
spans (one older than them) gives no intervals, and the readers then
return nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from .trace import Interval, _clip, _length, _union

STEP = "engine.step"
READBACK = "engine.readback"


def events(trace, name: str) -> List[Interval]:
    """Each host span named ``name`` that reaches into the kept window,
    whole and in order."""
    return sorted((s, s + d) for n, s, d in trace.host
                  if n == name and _clip([(s, s + d)], trace.keep))


def spans(trace, name: str) -> List[Interval]:
    """The union of the host spans named ``name``, inside the kept window."""
    return _union(_clip(events(trace, name), trace.keep))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of ``a`` outside ``b`` (both sorted and disjoint)."""
    out: List[Interval] = []
    for lo, hi in a:
        t = lo
        for c, d in b:
            if d <= t:
                continue
            if c >= hi:
                break
            if c > t:
                out.append((t, c))
            t = d
        if t < hi:
            out.append((t, hi))
    return out


def idle_ns(trace, iv: Sequence[Interval]) -> int:
    """Device-idle time inside ``iv`` (sorted, disjoint, in the window)."""
    return _length(subtract(iv, trace.busy_iv))


def instrumented(trace) -> bool:
    """Whether the traced program has the engine's spans (and with them
    the stable names of its pool and repair programs)."""
    return bool(events(trace, STEP))


def idle_split(trace) -> Optional[Dict[str, int]]:
    """The window's device-idle nanoseconds in three parts that sum to it:
    inside ``engine.readback`` spans, inside ``engine.step`` outside them
    (the engine's own host work), and outside every ``engine.step`` (the
    harness's submission and bookkeeping).  ``None`` without the spans."""
    steps = spans(trace, STEP)
    if not steps:
        return None
    reads = spans(trace, READBACK)
    return {
        "readback": idle_ns(trace, reads),
        "engine_host": idle_ns(trace, subtract(steps, reads)),
        "outside": idle_ns(trace, subtract(subtract(trace.keep, steps), reads)),
    }


def children_per_step(trace, name: str) -> List[int]:
    """For each ``engine.step`` in the window, how many spans named
    ``name`` start inside it."""
    kids = [a for a, _ in events(trace, name)]
    return [sum(1 for k in kids if s <= k < e) for s, e in events(trace, STEP)]
