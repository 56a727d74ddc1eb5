"""The control and the program's own readings of the number ``correct``
compares, over many seeds in one process (set-up is long):

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 20

For each seed: a run of the cell at its own load for ``--seconds``, then,
on the same sample of finished requests, the harness's verdict twice: on
the served tokens (the program's reading, under ``program``) and on the
tokens the reference computed in float8 puts first (the control's, as
``correct`` and ``checks``), which has to come out false.  One JSON line per
seed on stdout.  The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from bench.harness.registry import Registry  # noqa: E402
from bench.harness.runner import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m bench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    for seed in [int(x) for x in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = run_cell(Registry(), args.workload, seed, args.seconds, False,
                       t0, control=True, rate=args.rate)
        print(json.dumps({"seed": seed, "control": {"correct": res["correct"],
                                                    "checks": res["checks"]},
                          "program": res["program"],
                          "per_request": res["per_request"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
