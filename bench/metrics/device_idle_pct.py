"""device_idle_pct (%; device; moves itl_p99_ms): share of the traced window
in which no operation ran on the chip, the injection passes cut out."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
