"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Single-host execution path (smoke/real): builds the model from the registry,
streams batches from a token shard through the UMap data pipeline, runs the
Trainer with async checkpointing + restart.  On a real TPU cluster the same
entry runs under `jax.distributed.initialize()` with the production mesh
(``--mesh single|multi``) — the per-cell pjit assembly is exactly
launch/specs.build_cell, which the dry-run has already validated for every
(arch × shape).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \\
      --steps 100 --batch 8 --seq 64
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --mesh single \\
      --dry   # lower+compile the production train step, no execution
"""

from __future__ import annotations

import argparse
import sys
import tempfile
from pathlib import Path

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", default=None, help="int32 token shard file")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi"], default=None,
                    help="production mesh (requires matching device count)")
    ap.add_argument("--dry", action="store_true",
                    help="lower+compile the production step and exit")
    args = ap.parse_args(argv)

    if args.dry:
        # delegate to the dry-run driver (sets XLA_FLAGS before jax init)
        from .dryrun import run_cell
        rec = run_cell(args.arch, "train_4k", args.mesh == "multi",
                       Path("experiments/dryrun"))
        return 0 if rec["ok"] else 1

    from ..configs.registry import get_config, get_smoke_config
    from ..core import FileStore, UMapConfig
    from ..data.pipeline import lm_batches
    from ..train.optimizer import AdamWConfig
    from ..train.train_step import TrainConfig
    from ..train.trainer import Trainer, TrainerConfig
    from .runtime import device_summary, enable_compile_cache

    enable_compile_cache()
    print("device:", device_summary())

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.input_mode != "tokens" or cfg.is_encdec:
        print(f"{args.arch}: stub-frontend arch — use tests/examples for the "
              "embeds path; token training unsupported here", file=sys.stderr)
        return 2

    if args.data:
        shard = Path(args.data)
    else:
        tmp = Path(tempfile.mkdtemp(prefix="repro_train_"))
        shard = tmp / "tokens.bin"
        rng = np.random.default_rng(0)
        need = args.steps * args.batch * (args.seq + 1) + 1024
        v_eff = min(4096, cfg.vocab_size)
        probs = 1.0 / np.arange(1, v_eff + 1)
        probs /= probs.sum()
        rng.choice(v_eff, size=need, p=probs).astype(np.int32).tofile(shard)
        print(f"synthetic shard: {shard}")

    store = FileStore(str(shard))
    loader, reader = lm_batches(
        store, args.batch, args.seq,
        config=UMapConfig(page_size=1 << 20, buffer_size=32 << 20,
                          num_fillers=4, num_evictors=2, read_ahead=4,
                          eviction_policy="swa"))
    tcfg = TrainerConfig(
        train=TrainConfig(optimizer=AdamWConfig(
            learning_rate=args.lr, warmup_steps=max(10, args.steps // 10),
            total_steps=args.steps), loss_chunk=min(1024, args.seq)),
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=max(10, args.steps // 4),
        log_every=max(1, args.steps // 20))
    trainer = Trainer(cfg, tcfg)
    trainer.install_preemption_handler()
    if args.resume and trainer.try_resume():
        print(f"resumed from step {trainer.step}")
    result = trainer.fit(loader)
    for h in result["history"]:
        print(f"step {h['step']:6d} loss {h['loss']:.4f} "
              f"gnorm {h['grad_norm']:.3f} {h['tokens_per_s']:.0f} tok/s")
    reader.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
