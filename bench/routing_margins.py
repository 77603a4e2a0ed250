#!/usr/bin/env python3
"""Sound readings of a DeepSeek serve cell's ``worst_gap_spreads``, each
with the reference's routing margin at every compared position, to tell
how much of the gap comes from routing near-ties.

    python3 bench/routing_margins.py --workload <cell> --seeds 1,2,3 \
        --seconds 10

Runs the cell once per seed in one process.  For every position the check
compares, the reference also gives the least margin between a token's k-th
and (k+1)-th router probability over the MoE layers: a near-tie, which
rounding below float32 can flip to another expert.  Per seed it prints the
worst gap, the margin where it sits, and the worst gap over the positions
whose margin is at least 1e-4, 1e-3 and 1e-2.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import harness  # noqa: E402
from reference import deepseek_v2  # noqa: E402

THRESHOLDS = (1e-4, 1e-3, 1e-2)


def reading(workload: str, seed: int, seconds: float, **kw) -> dict:
    """One run of the cell with the margins of every compared position."""
    seen = []
    served_gaps = deepseek_v2.served_gaps

    def with_margins(arch, w, prompt, served, width, max_served,
                     control=False):
        gaps = served_gaps(arch, w, prompt, served, width, max_served,
                           control)
        seen.append((gaps[0], deepseek_v2.routing_margins(
            arch, w, prompt, served, width)))
        return gaps

    deepseek_v2.served_gaps = with_margins
    try:
        out = harness.run_cell(workload, seed, seconds, False, **kw)
    finally:
        deepseek_v2.served_gaps = served_gaps
    gap = np.concatenate([g for g, _ in seen])
    margin = np.concatenate([m for _, m in seen])
    worst = int(np.argmax(gap))
    return {"seed": seed, "correct": out["correct"],
            "worst_gap": float(gap[worst]),
            "margin_at_worst": float(margin[worst]),
            "positions": int(len(gap)),
            "worst_where_margin_at_least": {
                f"{t:g}": (float(gap[margin >= t].max())
                           if (margin >= t).any() else None)
                for t in THRESHOLDS},
            "positions_with_margin_under": {
                f"{t:g}": int((margin < t).sum()) for t in THRESHOLDS}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(reading(args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
