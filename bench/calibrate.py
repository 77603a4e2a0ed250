#!/usr/bin/env python3
"""Readings of a cell's control, from which the upper end of each limit of
``correct`` is set.

    python3 bench/calibrate.py --workload <cell> --control <name> \
        --seeds 1,2,3 --seconds 51

Runs the cell once per seed in one process with the control put in the
program's place by the driver's check: ``fp8`` for a serve cell (the
reference in float8 (e4m3) picks each next token at the same positions);
``bf16_state`` (the reference with bfloat16 parameters and moments) or
``half_batch`` (the reference with half of each batch left out) for the
training cell.  The harness judges the control as it judges the program,
so each line's ``correct`` has to read false.  The benchmark's own runs
never compute a control.
"""

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               options={"control": args.control})
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"], "failed": out["failed"],
                          "checks": {k: [c["value"], c["limit"], c.get("where")]
                                     for k, c in out["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
