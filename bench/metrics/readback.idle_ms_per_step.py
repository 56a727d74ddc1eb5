"""readback.idle_ms_per_step (ms; engine, serving/engine.py; moves
itl_p99_ms): device-idle time inside the engine's blocking device-to-host
readbacks (``engine.readback`` spans) per engine step of the traced window,
the injection passes cut out.  With ``engine_host.idle_ms_per_step`` and
the idle outside ``engine.step`` it sums to ``device_idle_pct`` of the
window (``bench/harness/spans.py``)."""
from bench.harness import spans


def read(run):
    t = run.trace
    if t is None or t.n_steps == 0:
        return None
    split = spans.idle_split(t)
    return None if split is None else 1e-6 * split["readback"] / t.n_steps
