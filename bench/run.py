"""Run one benchmark cell once: ``python3 -m bench.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` (see ``harness/runner.py``)."""
import time

T_START = time.perf_counter()

import sys  # noqa: E402

from bench.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
