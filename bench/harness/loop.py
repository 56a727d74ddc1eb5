"""The measured window: one open-loop client driving ``Engine`` in one loop.

Before each engine step the loop submits every request whose wall-clock due
time has passed.  Arrival times come from the schedule drawn before the
window opens, in seconds, never in engine steps, so a slow step delays the
server and not the clients.  Each emitted token is stamped with the end of
the step that produced it (the step reads its tokens back, so the device
work is done by then).

The simulated bit flips are outside the clock: the harness injects through
the engine's own entry point (``engine.space.inject`` over ``pool.tree``,
donated) between steps, blocks on the pool before and after, and stops its
clock for that long — the arrival schedule shifts by the same amount.  The
same holds for writing out a profiler trace.  ``now()`` is that clock.

Around each pass the loop also logs which requests took a flip that the
configuration lets through unrepaired: a lane the detector passes (finite,
below ``DETECTED``) that became the largest of its page, layer and KV head.
Such a flip is the configuration's own loss of accuracy, not the system's
fault, and ``check`` leaves those requests out of the comparison (PERF.md).

Each step's work is logged as the engine reports it: the context of every
request that decoded, and ``(q_start, q_len, last)`` of every prompt chunk,
read off each request's prefill progress before and after ``step()``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import trace as trace_lib

# The pool rule every cell states (the engine's default): a lane that is
# NaN, Inf or of magnitude 2**32 or more is detected and repaired.
DETECTED = 2.0 ** 32
# Length of the ``--trace 1`` run's profiler trace, mid-window.
TRACE_S = 3.0


@dataclasses.dataclass
class Log:
    window: Tuple[float, float]
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    lateness: List[float] = dataclasses.field(default_factory=list)
    token_times: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    # one entry per engine step: (t0, t1, decode contexts) ...
    steps: List[Tuple[float, float, List[int]]] = dataclasses.field(
        default_factory=list)
    # ... and the prompt chunks it ran, (q_start, q_len, last)
    chunks: List[List[Tuple[int, int, bool]]] = dataclasses.field(
        default_factory=list)
    # requests whose pages took a flip the detector lets through
    tainted: set = dataclasses.field(default_factory=set)
    counters: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)
    window_steps: Tuple[int, int] = (0, 0)
    traced_steps: Tuple[int, int] = (0, 0)
    inject_s: float = 0.0
    n_injections: int = 0
    paused_s: float = 0.0
    real_s: float = 0.0


class Clock:
    def __init__(self):
        self.origin = time.perf_counter()
        self.paused = 0.0

    def now(self) -> float:
        return time.perf_counter() - self.origin - self.paused


def _counters(engine) -> Dict[str, Any]:
    m = engine.metrics()
    return {k: m[k] for k in ("n_host_syncs", "scrubbed_bytes", "scrub_calls",
                              "nonfinite_logit_rows", "n_preemptions",
                              "tokens_emitted")}


@jax.jit
def page_peaks(tree):
    """Per page, layer and KV head of each pool leaf (pages, layers, rows,
    KV heads, lanes): the largest magnitude among the lanes the detector
    passes."""
    def one(leaf):
        a = jnp.abs(leaf.astype(jnp.float32))
        return jnp.where(a < DETECTED, a, 0.0).max(axis=(2, 4))  # NaN fails <

    return [one(leaf) for leaf in jax.tree.leaves(tree)]


@jax.jit
def risen(before, after):
    """Pages where some layer and head has a larger passed lane than before."""
    return jnp.stack([(a > b).any(axis=(1, 2))
                      for b, a in zip(before, after)]).any(axis=0)


def inject(engine, key, dose: float, log: Log) -> None:
    """One pass of flips over the pool, and the requests it left tainted."""
    before = page_peaks(engine.pool.tree)
    engine.pool.tree, _ = engine.space.inject(engine.pool.tree, key, dose,
                                              donate=True)
    hit = set(np.flatnonzero(np.asarray(risen(before, page_peaks(engine.pool.tree)))))
    for req in engine.sched.running:
        if hit.intersection(req.pages):
            log.tainted.add(req.rid)


def _prefill_progress(engine) -> List[Tuple[Any, int]]:
    """Each request yet without a first token, and what it has prefilled."""
    return [(r, r.prefill_pos or 0)
            for r in list(engine.sched.waiting) + engine.sched.running
            if not r.tokens]


def drive(engine, arrivals: Sequence, *, lead_in_s: float, seconds: float,
          ber_dose: float = 0.0, inject_every: int = 1, inject_key=None,
          trace_dir: Optional[str] = None) -> Log:
    """Replay ``arrivals`` (sorted by ``due_s``) from now until the window
    ``[lead_in_s, lead_in_s + seconds)`` closes."""
    w0, w1 = lead_in_s, lead_in_s + seconds
    log = Log(window=(w0, w1))
    trace_at = w0 + max(0.0, (seconds - TRACE_S) / 2)
    trace_state = 0 if trace_dir else 2          # 0 pending, 1 on, 2 done
    clock = Clock()
    ctx: Dict[int, int] = {}                     # rid -> context length
    nxt, n_steps = 0, 0
    window_open = False
    while True:
        t = clock.now()
        if t >= w1:
            break
        with TraceAnnotation("bench.submit"):
            while nxt < len(arrivals) and arrivals[nxt].due_s <= t:
                a = arrivals[nxt]
                rid = engine.add_request(a.prompt, a.max_new)
                log.lateness.append(t - a.due_s)
                ctx[rid] = len(a.prompt)
                if w0 <= a.due_s < w1:
                    log.due[rid] = a.due_s
                nxt += 1
        if trace_state == 0 and t >= trace_at:
            trace_lib.start(trace_dir)
            trace_state, log.traced_steps = 1, (n_steps, n_steps)
        elif trace_state == 1 and t >= trace_at + TRACE_S:
            log.traced_steps = (log.traced_steps[0], n_steps)
            p0 = time.perf_counter()
            jax.profiler.stop_trace()
            clock.paused += time.perf_counter() - p0
            log.paused_s += time.perf_counter() - p0
            trace_state = 2
        if not window_open and t >= w0:
            window_open = True
            log.counters["open"] = _counters(engine)
            log.window_steps = (n_steps, n_steps)
        if not engine.has_work:
            wake = [w1]
            if nxt < len(arrivals):
                wake.append(arrivals[nxt].due_s)
            if trace_state < 2:
                wake.append(trace_at if trace_state == 0 else trace_at + TRACE_S)
            time.sleep(max(0.0, min(wake) - t))
            continue
        if ber_dose > 0 and n_steps % inject_every == 0:
            jax.block_until_ready(engine.pool.tree)
            p0 = time.perf_counter()
            with TraceAnnotation("bench.inject"):
                inject_key, k = jax.random.split(inject_key)
                inject(engine, k, ber_dose, log)
                jax.block_until_ready(engine.pool.tree)
            dt = time.perf_counter() - p0
            clock.paused += dt
            log.inject_s += dt
            log.n_injections += 1
        with TraceAnnotation("bench.bookkeeping"):
            before = _prefill_progress(engine)
        t0 = clock.now()
        with TraceAnnotation("bench.step"):
            out = engine.step()
        t1 = clock.now()
        with TraceAnnotation("bench.bookkeeping"):
            decode_ctx = []
            for rid, toks in out["emitted"].items():
                times = log.token_times.setdefault(rid, [])
                if times:
                    decode_ctx.append(ctx[rid])
                ctx[rid] += len(toks)
                times.extend([t1] * len(toks))
            chunks = []
            for req, q0 in before:
                last = bool(req.tokens)
                q1 = len(req.prompt) if last else (req.prefill_pos or 0)
                if q1 > q0:
                    chunks.append((q0, q1 - q0, last))
            log.steps.append((t0, t1, decode_ctx))
            log.chunks.append(chunks)
            n_steps += 1
    if trace_state == 1:
        log.traced_steps = (log.traced_steps[0], n_steps)
        jax.profiler.stop_trace()
    log.window_steps = (log.window_steps[0], n_steps)
    log.counters["close"] = _counters(engine)
    log.real_s = time.perf_counter() - clock.origin
    return log
