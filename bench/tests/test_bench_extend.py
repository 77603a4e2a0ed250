"""A later change adds a cell and a per-layer metric by adding files and
entries only: here a new mix, its limits and a new metric reader, in a copy
of the benchmark's directory, run at smoke size on the CPU."""

import json
import shutil

import cells

NEW_MIX = {
    "driver": "serve_backlog",
    "shape_seed": 5,
    "requests": 60,
    "prompt": {"dist": "uniform", "min": 8, "max": 20},
    "output": {"dist": "uniform", "min": 16, "max": 24},
    "check": {"requests": 4},
}

NEW_METRIC = '''"""Active lanes per decode step of the window."""


def read(ctx):
    d = ctx.data["decode"]
    return d["lanes"] / d["steps"] if d["steps"] else None
'''


def test_new_cell_and_metric_from_new_files_only(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(cells.BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "testdata", "__pycache__"))
    (root / "traffic" / "decode_short.json").write_text(json.dumps(NEW_MIX))
    (root / "limits" / "qwen2-1.5b.decode_short.json").write_text(json.dumps(
        {"worst_gap_spreads": 0.05, "min_tokens_compared": 8}))
    (root / "metrics" / "decode.lanes_per_step.py").write_text(NEW_METRIC)
    spec = json.loads((cells.BENCH.parent / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen2-1.5b.decode_short",
                              "config": "qwen2-1.5b", "traffic": "decode_short",
                              "chips": 1, "why": "short decode"})
    spec["per_layer"].append({"name": "decode.lanes_per_step", "unit": "lanes",
                              "better": "higher", "source": "program_counter",
                              "layer": "serve engine", "moves": "tokens_per_s",
                              "workloads": ["qwen2-1.5b.decode_short"]})
    out = cells.harness.run_cell("qwen2-1.5b.decode_short", cells.SEED, 0.3,
                                 True, spec=spec, root=root, smoke=True,
                                 require_chip=False)
    assert out["correct"], out["checks"]
    lanes = out["metrics"]["decode.lanes_per_step"]["value"]
    assert 1 <= lanes <= 4                      # the smoke engine's max_batch
