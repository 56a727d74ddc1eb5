"""page_reset.device_ms_per_step (ms; pool and reactive repair,
serving/pool.py; moves itl_p99_ms): device time of the pool's page-reset
program (``jit_pool_reset_pages``, run on every page allocation) per engine
step in the traced window.  Nothing to read in a program without the
stable names."""
import re

from bench.harness import spans

PROGRAM = re.compile(r"^jit_pool_reset_pages\(")


def read(run):
    t = run.trace
    if t is None or t.n_steps == 0 or not spans.instrumented(t):
        return None
    return 1e3 * sum(t.module_durations(PROGRAM.search)) / t.n_steps
