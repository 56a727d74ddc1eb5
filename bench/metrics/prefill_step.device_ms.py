"""prefill_step.device_ms (ms; model step, the engine's chunked paged
prefill program; moves ttft_p90_ms): mean device time of one run of the
prefill-chunk program in the traced window."""
import re

PROGRAM = re.compile(r"prefill_step")


def read(run):
    if run.trace is None:
        return None
    d = run.trace.module_durations(PROGRAM.search)
    return 1e3 * sum(d) / len(d) if d else None
