"""What the metric readers share: the window's tokens, steps and the traced
steps' work, read off a run's log (``loop.Log``)."""
from __future__ import annotations

from typing import List, Tuple

from . import counts


def window_token_count(run) -> int:
    w0, w1 = run.log.window
    return sum(1 for times in run.log.token_times.values() for t in times
               if w0 <= t < w1)


def window_steps(run) -> List[Tuple[float, float, List[int]]]:
    i0, i1 = run.log.window_steps
    return run.log.steps[i0:i1]


def traced_steps(run) -> List[Tuple[float, float, List[int]]]:
    i0, i1 = run.log.traced_steps
    return run.log.steps[i0:i1]


def traced_prefill_chunks(run) -> List[Tuple[int, int, bool]]:
    """``(q_start, q_len, last)`` of every prompt chunk the traced steps
    ran, as the loop logged them from the engine's prefill progress."""
    i0, i1 = run.log.traced_steps
    return [c for step in run.log.chunks[i0:i1] for c in step]


def traced_step_flops(run) -> int:
    """Model operations the traced steps' tokens need (``counts``)."""
    s = run.cell.shapes
    f = sum(counts.token_flops(s, c, readout=True)
            for _, _, ctx in traced_steps(run) for c in ctx)
    f += sum(counts.prefill_chunk_flops(s, q0, n, last)
             for q0, n, last in traced_prefill_chunks(run))
    return f
