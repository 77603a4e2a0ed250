import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run driver (deliverable e).

For one (arch × shape × mesh) cell:
  * builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * assembles the jitted entry point with explicit in/out shardings,
  * ``.lower(**input_specs).compile()`` — ShapeDtypeStruct only, no
    allocation,
  * records memory_analysis / cost_analysis / per-collective byte totals
    into a JSON under experiments/dryrun/.

The XLA_FLAGS line above is the VERY FIRST statement so the 512 placeholder
devices exist before jax locks the backend.  Never import this module from
tests (they must see 1 device) — it is a __main__-style entry point.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from ..configs.base import SHAPES
from ..configs.registry import get_config, runnable_cells
from ..distributed.sharding import use_mesh
from .mesh import make_production_mesh, rules_for

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(tok_dtype: str, tok_dims: str) -> int:
    n = 1
    if tok_dims:
        for d in tok_dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(tok_dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes per collective kind from compiled HLO text."""
    totals = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            # match op invocations incl. async -start forms; skip -done
            marker = f" {kind}("
            marker_start = f" {kind}-start("
            if marker in line or marker_start in line:
                op = marker_start if marker_start in line else marker
                args = line.split(op, 1)[1]
                # operands are shape tokens inside the call parens (first level)
                depth, end = 1, 0
                for i, ch in enumerate(args):
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            end = i
                            break
                for m in _SHAPE_RE.finditer(args[:end]):
                    totals[kind] += _shape_bytes(m.group(1), m.group(2))
                counts[kind] += 1
                break
    totals["total"] = sum(totals[k] for k in _COLLECTIVES)
    totals["counts"] = counts
    return totals


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             overrides: dict | None = None, variant: str = "") -> dict:
    from ..launch.specs import build_cell  # deferred: after XLA_FLAGS

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if variant:
        mesh_name = f"{mesh_name}__{variant}"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "devices": int(mesh.size), "ok": False,
        "overrides": overrides or {}, "variant": variant,
    }
    t0 = time.time()
    try:
        with use_mesh(mesh, rules_for(shape.kind, cfg)):
            jitted, args = build_cell(cfg, shape, mesh)
            lowered = jitted.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            hlo = compiled.as_text()
            coll = collective_bytes(hlo)
            # persist the compiled HLO for the roofline walker
            # (scan-over-layers keeps modules small; ~1 MB gz each)
            import gzip
            out_dir.mkdir(parents=True, exist_ok=True)
            hlo_path = out_dir / f"{arch}__{shape_name}__{mesh_name}.hlo.gz"
            with gzip.open(hlo_path, "wt") as f:
                f.write(hlo)
        rec.update(
            ok=True,
            lower_s=round(t_lower - t0, 2),
            compile_s=round(t_compile - t_lower, 2),
            flops=float(cost.get("flops", -1)) if cost else -1,
            bytes_accessed=float(cost.get("bytes accessed", -1)) if cost else -1,
            collectives=coll,
            hlo_lines=hlo.count("\n"),
        )
        if mem is not None:
            rec["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
                "output_bytes": getattr(mem, "output_size_in_bytes", -1),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", -1),
            }
    except Exception as e:  # noqa: BLE001 - record failures, don't crash sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)

    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    out.write_text(json.dumps(rec, indent=1))
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {arch} × {shape_name} × {mesh_name} "
          f"({rec['total_s']}s)", flush=True)
    if not rec["ok"]:
        print(rec["error"], flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="", help="suffix for A/B artifacts")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. moe_shard_local=false")
    args = ap.parse_args(argv)
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(
            v.lower(), int(v) if v.lstrip("-").isdigit() else v)
    rec = run_cell(args.arch, args.shape, args.multi_pod, Path(args.out),
                   overrides, args.variant)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
