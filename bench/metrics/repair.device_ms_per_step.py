"""repair.device_ms_per_step (ms; pool and reactive repair,
serving/repair.py, runtime/plan.py, kernels/scrub.py; moves itl_p99_ms):
device time of the repair plan's programs (``jit_repair_pages``,
``jit_repair_tree``, ``jit_repair_reference``) per engine step in the
traced window; 0 when no page faulted in it.  The injection pass is
``jit_inject`` and left out by name as well as by the cut.  Nothing to
read at BER 0, nor in a program without the stable names."""
import re

from bench.harness import spans

PROGRAM = re.compile(r"^jit_repair_")


def read(run):
    t = run.trace
    if t is None or run.cell.ber <= 0 or t.n_steps == 0:
        return None
    if not spans.instrumented(t):
        return None
    return 1e3 * sum(t.module_durations(PROGRAM.search)) / t.n_steps
