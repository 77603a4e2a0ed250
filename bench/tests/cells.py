"""Running a cell at its configuration's smoke size on the CPU, for tests."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SEED = 2**33 + 17          # more than 32 bits, as the checks draw them


def run(cell: str, seconds: float = 0.3, trace: bool = False, **kw) -> dict:
    return harness.run_cell(cell, SEED, seconds, trace, smoke=True,
                            require_chip=False, **kw)
