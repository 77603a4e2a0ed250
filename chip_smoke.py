#!/usr/bin/env python3
"""Chip smoke test: the paged serve and out-of-core training paths, once, on
one TPU, at smollm-135m's full width with seeded random weights.

    python3 chip_smoke.py          # from the repository root, on a TPU host

Phases (one process, no child processes; any failed check raises):

  serve      ``ServeEngine`` answers 8 requests (prompts of 64-256 tokens,
             16 new tokens each); every generated token is checked against
             a float32 reference forward over prompt + generated prefix.
  paging     ``OOCTrainer`` takes 2 steps with its parameters paged through
             a buffer smaller than the parameters (``RegionLayerSource`` ->
             ``page_scatter``/``page_gather``) and 2 steps resident; losses,
             parameters and moments must be bitwise equal.

The paging phase also checks that the lowered program holds the Pallas
kernels (``tpu_custom_call``), not the reference.  The script exits non-zero
when JAX finds no TPU.  Its last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
import math
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.page_gather.ops import page_gather, page_scatter  # noqa: E402
from repro.launch.runtime import (  # noqa: E402
    compile_cache_stats,
    device_summary,
    enable_compile_cache,
)
from repro.models import transformer as T  # noqa: E402
from repro.serve.engine import EngineConfig, Request, ServeEngine  # noqa: E402
from repro.train.ooc import OOCTrainer, OOCTrainerConfig  # noqa: E402
from repro.train.train_step import TrainConfig  # noqa: E402

ARCH = "smollm-135m"

# Serve check.  The engine computes in bfloat16 (8 mantissa bits): every
# layer rounds its activations with a relative error of up to 2^-9, and over
# 30 layers those roundings add up to a few percent of the hidden state,
# which moves each logit by a few percent of the spread (standard deviation)
# of the logits.  Near-ties between the top tokens are therefore decided
# differently than in float32, so the check is on logits, not on tokens: the
# reference logit of the engine's token must be within a quarter of the
# spread of the reference's largest logit.  A token that is not among the
# top ones sits about 4 spreads below the largest of 49,152 logits.
SERVE_GAP_TOL_SPREADS = 0.25


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def _check_kernel_lowered(op, impl: str, *args) -> None:
    """``op`` dispatched with ``impl``, as the phase called it, must lower
    to the Pallas kernel and not to the jnp reference."""
    text = jax.jit(partial(op, impl=impl)).lower(*args).as_text()
    _check("tpu_custom_call" in text,
           f"{op.__name__}(impl={impl!r}): lowered program has no "
           f"tpu_custom_call")


# ------------------------------------------------------------------- serve


def _reference_logits(cfg: ModelConfig, params: dict, tokens: np.ndarray,
                      at: np.ndarray) -> np.ndarray:
    """float32 logits [B, n, V] of the full forward over ``tokens`` [B, S]
    at positions ``at`` [B, n] (right padding of ``tokens`` is harmless:
    attention is causal)."""
    cfg32 = cfg.replace(compute_dtype="float32")

    @jax.jit
    def run(params, tokens, at):
        hidden, _ = T.forward_train(cfg32, params, {"tokens": tokens})
        picked = jnp.take_along_axis(hidden, at[..., None], axis=1)
        return T.lm_logits(cfg32, params, picked)

    with jax.default_matmul_precision("highest"):
        return np.asarray(run(params, jnp.asarray(tokens), jnp.asarray(at)),
                          np.float32)


def phase_serve(cfg: ModelConfig, *, requests: int = 8,
                prompt_lens=(64, 256), new_tokens: int = 16,
                page_size: int = 16, max_batch: int = 8,
                prefill_bucket: int = 64, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    params = T.init_params(cfg, jax.random.key(seed))
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=requests)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in lens]
    max_pages = -(-(int(lens.max()) + new_tokens) // page_size) + 2
    need = sum(-(-(int(n) + new_tokens) // page_size) + 1 for n in lens)
    # twice what the requests take, so the admission watermarks never bind
    ecfg = EngineConfig(max_batch=max_batch, page_size=page_size,
                        num_pages=2 * need + 1, max_pages_per_seq=max_pages,
                        prefill_bucket=prefill_bucket)
    eng = ServeEngine(cfg, params, ecfg)
    t0 = time.perf_counter()
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=new_tokens))
    eng.run_until_drained(max_steps=10 * requests * new_tokens)
    serve_s = time.perf_counter() - t0
    done = sorted(eng.finished, key=lambda r: r.rid)
    _check(len(done) == requests and not eng.active and not eng.waiting,
           f"serve: {len(done)}/{requests} requests finished")
    for r in done:
        _check(not r.expired and len(r.generated) == new_tokens,
               f"serve: request {r.rid} expired={r.expired} "
               f"generated {len(r.generated)}/{new_tokens}")

    # float32 reference over prompt + generated prefix, all requests at once
    width = int(lens.max()) + new_tokens
    tokens = np.zeros((requests, width), np.int32)
    at = np.zeros((requests, new_tokens), np.int32)
    for i, r in enumerate(done):
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        at[i] = len(r.prompt) - 1 + np.arange(new_tokens)
    logits = _reference_logits(cfg, params, tokens, at)[..., :cfg.vocab_size]
    got = np.asarray([r.generated for r in done])
    chosen = np.take_along_axis(logits, got[..., None], axis=-1)[..., 0]
    gap = logits.max(axis=-1) - chosen
    spread = logits.std(axis=-1)
    worst = float((gap / spread).max())
    _check(worst <= SERVE_GAP_TOL_SPREADS,
           f"serve: a generated token's reference logit is {worst:.3f} "
           f"spreads below the largest (tolerance {SERVE_GAP_TOL_SPREADS})")
    return {"requests": requests, "tokens": int(got.size),
            "prompt_lens": [int(n) for n in lens],
            "argmax_agreement": float((gap == 0).mean()),
            "worst_gap_spreads": worst,
            "tolerance_spreads": SERVE_GAP_TOL_SPREADS,
            "engine_steps": eng.stats["steps"],
            "evictions": eng.stats["evictions"],
            "serve_s": serve_s}


# ------------------------------------------------------------------ paging


def _train_batches(cfg: ModelConfig, steps: int, batch: int, seq: int,
                   seed: int) -> list:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        toks = rng.integers(0, cfg.vocab_size, size=(batch, seq + 1))
        out.append({"tokens": toks[:, :-1].astype(np.int32),
                    "labels": toks[:, 1:].astype(np.int32)})
    return out


def _run_trainer(cfg, ocfg, paged, batches, seed):
    tr = OOCTrainer(cfg, TrainConfig(), ocfg, rng=jax.random.key(seed),
                    paged=paged)
    try:
        losses = [tr.step(b)["loss"] for b in batches]
        info = {"staging_copies": tr.staging_copies}
        if paged:
            region = tr.source.region
            info["params_buffer_bytes"] = region.service.config.buffer_size
            info["params_bytes"] = region.size
            info["params_region"] = region.stats()
            info["pool_shape"] = (tr.source.pool_pages,) + tr.source.page_shape
        return losses, tr.state_dict(), info
    finally:
        tr.close()


def _specs_geometry(cfg: ModelConfig, page_size: int, seed: int):
    """(total params pages, largest leaf pages) of the packed fp32
    parameters, each leaf starting on a page boundary."""
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(seed)))
    pages = [-(-math.prod(s.shape) * 4 // page_size)
             for s in jax.tree_util.tree_leaves(shapes)]
    return sum(pages), max(pages)


def phase_paging(cfg: ModelConfig, *, impl: str, steps: int = 2,
                 batch: int = 2, seq: int = 128,
                 page_size: int = 512 * 1024, seed: int = 0) -> dict:
    total, largest = _specs_geometry(cfg, page_size, seed)
    slots = 2 * largest          # the least that can lease the largest leaf
    _check(slots < total,
           f"paging: parameters of {total} pages cannot be oversubscribed "
           f"(largest leaf {largest} pages)")
    ocfg = OOCTrainerConfig(page_size=page_size, params_buffer_pages=slots,
                            pool_pages=slots, kernel_impl=impl,
                            total_steps=steps)
    batches = _train_batches(cfg, steps, batch, seq, seed)
    t0 = time.perf_counter()
    loss_r, state_r, _ = _run_trainer(cfg, ocfg, False, batches, seed)
    t1 = time.perf_counter()
    loss_p, state_p, info = _run_trainer(cfg, ocfg, True, batches, seed)
    t2 = time.perf_counter()

    _check(info["params_buffer_bytes"] < info["params_bytes"],
           "paging: the params buffer holds all parameters")
    _check(info["staging_copies"] == 0,
           f"paging: {info['staging_copies']} staging copies")
    _check(all(np.isfinite(loss_r)), f"paging: losses {loss_r}")
    _check(loss_p == loss_r, f"paging: losses differ: paged {loss_p} resident {loss_r}")
    leaves_r = jax.tree_util.tree_leaves(state_r)
    leaves_p = jax.tree_util.tree_leaves(state_p)
    _check(len(leaves_r) == len(leaves_p), "paging: state trees differ")
    for a, b in zip(leaves_p, leaves_r):
        _check(np.array_equal(np.asarray(a), np.asarray(b)),
               "paging: paged and resident state differ")
    if impl == "pallas":
        # the two kernels as the param source calls them
        pool = jax.ShapeDtypeStruct(info["pool_shape"], jnp.float32)
        ids = jax.ShapeDtypeStruct((largest,), jnp.int32)
        pages = jax.ShapeDtypeStruct((largest,) + info["pool_shape"][1:],
                                     jnp.float32)
        _check_kernel_lowered(page_gather, impl, pool, ids)
        _check_kernel_lowered(page_scatter, impl, pool, ids, pages)
    st = info["params_region"]
    return {"losses": [float(x) for x in loss_p],
            "params_bytes": info["params_bytes"],
            "params_buffer_bytes": info["params_buffer_bytes"],
            "page_size": page_size,
            "params_region": {k: st.get(k) for k in
                              ("demand_faults", "evictions", "leases")},
            "staging_copies": info["staging_copies"],
            "bitwise_equal": True,
            "resident_s": t1 - t0, "paged_s": t2 - t1}


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    device = device_summary()
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found: JAX runs on {device['platform']!r}; "
              f"this script checks the chip path and needs a TPU",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    cfg = get_config(ARCH)
    phases = (("serve", lambda: phase_serve(cfg)),
              ("paging", lambda: phase_paging(cfg, impl="pallas")))
    for name, run in phases:
        t0 = time.perf_counter()
        result = run()
        result["seconds"] = time.perf_counter() - t0
        print(f"phase {name} ok: {json.dumps(result)}", flush=True)
    print(f"compile cache {cache_dir}: {json.dumps(compile_cache_stats())}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
