"""host_syncs_per_step (1/step; engine, serving/engine.py; moves itl_p99_ms):
blocking device-to-host readbacks per engine step over the window, from
``Engine.metrics()["n_host_syncs"]``."""


def read(run):
    i0, i1 = run.log.window_steps
    c = run.log.counters
    if i1 <= i0 or "open" not in c:
        return None
    return (c["close"]["n_host_syncs"] - c["open"]["n_host_syncs"]) / (i1 - i0)
