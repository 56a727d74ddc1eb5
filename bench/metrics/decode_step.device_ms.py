"""decode_step.device_ms (ms; model step, models/transformer_lm.py under the
engine's paged decode program; moves itl_p99_ms): mean device time of one
run of the decode step program in the traced window."""
import re

PROGRAM = re.compile(r"paged_step")


def read(run):
    if run.trace is None:
        return None
    d = run.trace.module_durations(PROGRAM.search)
    return 1e3 * sum(d) / len(d) if d else None
