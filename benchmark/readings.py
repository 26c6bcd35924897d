#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds in one
process: the program's (sound runs) and, with ``--control``, the
control's (the plain reference in the program's place, one precision
below the configuration's).  The limits in the configuration files are
set from these readings.

    python3 benchmark/readings.py --workload pde2d-cg --seeds 1,2,3 --seconds 3
    python3 benchmark/readings.py --workload pde2d-cg --seeds 1,2,3 --seconds 3 --control

Each seed prints one line with its compared numbers; the last line
gives, for each number, the largest reading over the seeds and the
smallest.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell, devices = bench_run.prepare(args.workload)
    from benchmark import harness

    readings: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, seed, args.seconds, False, devices,
                               control=args.control)
        line = {"seed": seed, "control": args.control,
                "correct": res["correct"], "ops": res["attempted"],
                "checks": res["checks"], "error": res.get("error")}
        harness.emit(line)
        for name, c in res["checks"].items():
            readings.setdefault(name, []).append(c["value"])
    harness.emit({"workload": args.workload, "control": args.control,
                  "seeds": len(args.seeds.split(",")),
                  "largest": {k: max(v) for k, v in readings.items()},
                  "smallest": {k: min(v) for k, v in readings.items()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
