"""engine_host.idle_ms_per_step (ms; engine, serving/engine.py; moves
itl_p99_ms): device-idle time inside ``engine.step`` spans and outside
their ``engine.readback`` spans — the engine's own host work (scheduling,
batch building, dispatch) while the chip waits — per engine step of the
traced window, the injection passes cut out."""
from bench.harness import spans


def read(run):
    t = run.trace
    if t is None or t.n_steps == 0:
        return None
    split = spans.idle_split(t)
    return None if split is None else 1e-6 * split["engine_host"] / t.n_steps
