#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell's engine sustains.

    python3 bench/sweep.py --workload <cell> --rates 4,5,6 --requests 163

Runs the cell once per rate in one process and prints, per rate, the serve
metrics, the requests still waiting when the window closed, the 95th
percentile of time to first token and its median over the requests due in
each third of the window, and how late the generator ran.  A rate is sustained when the queue does not
grow through the window: the thirds' medians stay level.

With ``--requests`` every rate serves the same requests as the cell does
(the mix's sizes for that many arrivals) in ``requests / rate`` seconds, so
no rate brings prompt lengths that set-up has to compile anew; without it,
each rate runs ``--seconds`` and draws its own.  The cell's mix then fixes
about four fifths of the highest sustained rate as ``rate_per_s``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import harness  # noqa: E402

SERVE_METRICS = ("tokens_per_s", "itl_p95_ms", "setup_s")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    spec = json.loads(harness.SPEC.read_text())
    # every serve metric, whichever the cell itself reports
    spec["end_to_end"] = [dict(m, workloads=[args.workload])
                          for m in spec["end_to_end"]
                          if m["name"] in SERVE_METRICS]
    for rate in [float(r) for r in args.rates.split(",")]:
        seconds = args.requests / rate if args.requests else args.seconds
        out = harness.run_cell(args.workload, args.seed, seconds, False,
                               spec=spec, t_start=time.perf_counter(),
                               mix_overrides={"rate_per_s": rate})
        m = {k: v["value"] for k, v in out["metrics"].items()}
        print(json.dumps({"rate_per_s": rate, "seconds": seconds, **m,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "load": out.get("load")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
