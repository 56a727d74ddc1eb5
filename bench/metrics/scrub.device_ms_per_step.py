"""scrub.device_ms_per_step (ms; pool and reactive repair, serving/pool.py,
serving/repair.py, kernels/scrub.py; moves itl_p99_ms): device time of the
reactive page-scrub programs per engine step in the traced window (0 when
no page faulted in it).  Nothing to read at BER 0.

The repair plan's programs (``runtime/plan.py``) are all named ``jit_fn``
in the trace; the injection pass is one of them, but its spans are cut out
of the window, so what is left is the scrub."""
import re

PROGRAM = re.compile(r"^jit_fn\(")


def read(run):
    if run.trace is None or run.cell.ber <= 0 or run.trace.n_steps == 0:
        return None
    return 1e3 * sum(run.trace.module_durations(PROGRAM.search)) / run.trace.n_steps
