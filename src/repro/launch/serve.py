"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``.

Continuous batching over the paged KV cache (serve/engine.py) with a
synthetic request stream; prints throughput and UMap pool telemetry.
``--dry`` lowers+compiles the production decode step (decode_32k cell)
instead of executing.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \\
      --requests 16 --max-new 16 --page-size 16
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--deadline-s", type=float, default=120.0)
    ap.add_argument("--mesh", choices=["single", "multi"], default=None)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args(argv)

    if args.dry:
        from .dryrun import run_cell
        rec = run_cell(args.arch, "decode_32k", args.mesh == "multi",
                       Path("experiments/dryrun"))
        return 0 if rec["ok"] else 1

    import jax

    import repro.models as M
    from ..configs.registry import get_config, get_smoke_config
    from ..serve.engine import EngineConfig, Request, ServeEngine
    from .runtime import device_summary, enable_compile_cache

    enable_compile_cache()
    print("device:", device_summary())

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.input_mode != "tokens" or cfg.is_encdec:
        print(f"{args.arch}: engine demo targets decoder-only token models",
              file=sys.stderr)
        return 2
    params = M.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, EngineConfig(
        max_batch=args.max_batch, page_size=args.page_size,
        num_pages=args.num_pages,
        max_pages_per_seq=max(32, (args.max_new + 64) // args.page_size + 4),
        prefill_bucket=32))

    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(args.requests):
        L = int(rng.integers(4, 24))
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, size=L).astype(np.int32),
            max_new_tokens=args.max_new, deadline_s=args.deadline_s))
    eng.run_until_drained(max_steps=50_000)
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in eng.finished)
    print(f"served {len(eng.finished)}/{args.requests} requests, "
          f"{toks} tokens in {dt:.2f}s ({toks / max(dt, 1e-9):.1f} tok/s)")
    print("engine:", eng.stats)
    print(f"pool: {eng.allocator.used_pages}/{eng.allocator.num_pages} pages "
          f"({args.page_size} tokens/page)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
