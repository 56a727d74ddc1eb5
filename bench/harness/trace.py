"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
small event record (kept as JSON for the reducer's test):

    {"device": {"ops": [[op_name, start_ns, dur_ns], ...],
                "modules": [[name, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``device`` holds the first TPU plane's ("/device:TPU:0") "XLA Ops" and
"XLA Modules" lines; ``host`` the events of the host thread that ran the harness (the one
holding its ``bench.*`` spans).  ``Trace`` reduces it.  The window runs from
the first ``bench.step`` span to the end of the last; the ``bench.inject``
spans (the simulated bit flips, which stand in for hardware that flips for
free) are cut out of the window, the busy time and every sum.
"""
from __future__ import annotations

import glob
import os
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[int, int]


def extract(trace_dir: str) -> Dict[str, object]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device = {"ops": [], "modules": []}
    host: List[list] = []
    dev_planes = sorted(
        (p for p in pd.planes if p.name.startswith("/device:TPU:")),
        key=lambda p: p.name,
    )
    if dev_planes:
        for line in dev_planes[0].lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key:
                device[key] = [[_op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                               for e in line.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            if any(e[0].startswith("bench.") for e in evs):
                host = evs
                break
    return {"device": device, "host": host}


def _op_name(name: str) -> str:
    """An op event is named by its HLO text ("%paged_prefill_raw.12 =
    (f32[...]) custom-call(...)"), whose operands name other ops: keep the
    op's own name ("paged_prefill_raw.12")."""
    return name.split(" = ", 1)[0].lstrip("%")


# control-flow ops span the ops they run; they are not work of their own
_CONTAINERS = ("while", "conditional", "call")


def start(trace_dir: str) -> None:
    """Start the profiler with JAX's Python function tracer off: the
    harness's ``TraceAnnotation`` spans and JAX's own host events label the
    gaps, at a fraction of the Python tracer's cost."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: Sequence[Interval], keep: Sequence[Interval]) -> List[Interval]:
    """Parts of ``iv`` inside the union ``keep`` (both sorted, disjoint)."""
    out = []
    for a, b in iv:
        for c, d in keep:
            lo, hi = max(a, c), min(b, d)
            if lo < hi:
                out.append((lo, hi))
    return out


def _length(iv: Sequence[Interval]) -> int:
    return sum(b - a for a, b in iv)


class Trace:
    def __init__(self, record: Dict[str, object]):
        self.ops = record["device"]["ops"]
        self.modules = record["device"]["modules"]
        self.host = record["host"]
        steps = [(s, s + d) for n, s, d in self.host if n == "bench.step"]
        if not steps:
            raise ValueError("no bench.step span in the trace")
        start = min(a for a, _ in steps)
        end = max(b for _, b in steps)
        cut = _union([(s, s + d) for n, s, d in self.host if n == "bench.inject"])
        keep, t = [], start
        for a, b in cut:
            if b <= start or a >= end:
                continue
            if a > t:
                keep.append((t, a))
            t = max(t, b)
        if t < end:
            keep.append((t, end))
        self.keep = keep
        self.n_steps = len(steps)
        self.window_ns = _length(keep)
        self.busy_iv = _union(_clip(_union([(s, s + d) for _, s, d in self.ops]), keep))
        self.busy_ns = _length(self.busy_iv)

    # ----------------------------------------------------------------- sums
    def _events(self, events, match: Callable[[str], bool]):
        return [(n, s, d) for n, s, d in events if match(n)
                and _clip([(s, s + d)], self.keep)]

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Device time of the ops whose name ``match``es, inside the window."""
        return sum(_length(_clip([(s, s + d)], self.keep))
                   for _, s, d in self._events(self.ops, match)) * 1e-9

    def module_durations(self, match: Callable[[str], bool]) -> List[float]:
        """Seconds of each program run whose module name ``match``es."""
        return [d * 1e-9 for _, _, d in self._events(self.modules, match)]

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9

    # ------------------------------------------------------------ breakdown
    def top_ops(self, n: int = 10) -> List[list]:
        tot: Dict[str, int] = {}
        for name, s, d in self.ops:
            if name.split(".")[0] in _CONTAINERS:
                continue
            t = _length(_clip([(s, s + d)], self.keep))
            if t:
                tot[name] = tot.get(name, 0) + t
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def _host_label(self, t: int) -> str:
        """The harness span, and the innermost host event in it, at ``t``."""
        inside = [(d, n) for n, s, d in self.host if s <= t < s + d]
        if not inside:
            return "host idle"
        inside.sort()
        outer = next((n for _, n in reversed(inside) if n.startswith("bench.")),
                     None)
        inner = inside[0][1]
        if outer is None or outer == inner:
            return inner
        return f"{outer} > {inner}"

    def idle_gaps(self, n: int = 10) -> List[list]:
        gaps = []
        for lo, hi in self.keep:
            t = lo
            for a, b in self.busy_iv:
                if b <= lo or a >= hi:
                    continue
                if a > t:
                    gaps.append((a - t, t))
                t = max(t, b)
            if t < hi:
                gaps.append((hi - t, t))
        gaps.sort(reverse=True)
        return [[self._host_label(t + d // 2), d * 1e-9] for d, t in gaps[:n]]
