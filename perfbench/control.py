"""``python3 perfbench/control.py --workload <cell> --seeds 1,2,3 [--scale tiny]``

The control and the planted faults at the cell's own size: the plain
reference, put in the program's place, in the precision below the one the
configuration states and with each fault a cell of its kind can have (the
kind's ``STAND_INS``). It runs no window, and a benchmark run never runs
it. One JSON line per seed and stand-in, every number compared
beside its limit; the upper readings in PERF.md come from here.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from perfbench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--scale", choices=("cell", "tiny"), default="cell")
    args = ap.parse_args()
    kind = harness.load_cell(args.workload)["kind"]
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.probe_only_run(args.workload, seed, kind.TINY if args.scale == "tiny" else None)
        for precision, fault in kind.STAND_INS:
            checks = run.control(precision, fault)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "precision": precision, "fault": fault,
                "failed": [k for k, c in checks.items() if c["value"] > c["limit"]],
                "checks": {k: c["value"] for k, c in checks.items()},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
