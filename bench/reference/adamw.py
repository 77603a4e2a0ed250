"""Plain training reference: next-token cross-entropy with a z-loss, and
AdamW with global-norm clipping, linear warm-up and cosine decay, in
float32 over the reference transformer (``reference/llm.py``).

``state_dtype="bfloat16"`` is the control: parameters and moments are
held in bfloat16 between steps, the step below the float32 state that the
configuration states.  ``half_batch=True`` plants a fault: the loss is the
mean over the first half of each batch only.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference import llm


def loss(arch: dict, z_loss: float, w: dict, tokens, labels):
    h = llm.hidden(arch, w, tokens, remat=True)
    lg = llm.logits(arch, w, h)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold) + z_loss * jnp.mean(lse * lse)


def lr_at(opt: dict, step: int) -> float:
    """Learning rate of 0-based ``step``."""
    warm = min(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    t = min(1.0, max(0.0, (step - opt["warmup_steps"])
                     / max(1, opt["total_steps"] - opt["warmup_steps"])))
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + np.cos(np.pi * t))
    return opt["learning_rate"] * warm * cos


@partial(jax.jit, static_argnums=(0, 1))
def _grad(arch_items, z_loss, w, tokens, labels):
    return jax.value_and_grad(partial(loss, dict(arch_items), z_loss))(
        w, tokens, labels)


@partial(jax.jit, static_argnums=(0,))
def _update(opt_items, w, m, v, g, step, lr):
    opt = dict(opt_items)
    b1, b2 = opt["beta1"], opt["beta2"]
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["grad_clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    bc1 = 1 - b1 ** (step + 1.0)
    bc2 = 1 - b2 ** (step + 1.0)
    w = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + opt["eps"])
                                  + opt["weight_decay"] * p), w, m, v)
    return w, m, v, g


def train(arch: dict, objective: dict, opt: dict, w0: dict, batches,
          state_dtype: str = "float32", half_batch: bool = False) -> dict:
    """Run ``len(batches)`` steps from weights ``w0`` (float32, benchmark
    layout).  Returns each step's loss, the first step's clipped gradient
    norm of every leaf, and every leaf's norm of change after the last
    step; leaves are keyed by their path in the layout."""
    arch_items = tuple(sorted(arch.items()))
    opt_items = tuple(sorted(opt.items()))
    sdt = jnp.dtype(state_dtype)
    w = jax.tree.map(lambda a: a.astype(sdt), w0)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, sdt), w0)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, sdt), w0)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for step, b in enumerate(batches):
            tokens, labels = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
            if half_batch:
                tokens, labels = tokens[:len(tokens) // 2], labels[:len(labels) // 2]
            f32 = partial(jax.tree.map, lambda a: a.astype(jnp.float32))
            lval, g = _grad(arch_items, objective["z_loss_coeff"], f32(w),
                            tokens, labels)
            w, m, v, gc = _update(opt_items, f32(w), f32(m), f32(v), g,
                                  jnp.float32(step), jnp.float32(lr_at(opt, step)))
            w, m, v = (jax.tree.map(lambda a: a.astype(sdt), t) for t in (w, m, v))
            losses.append(float(lval))
            if first_grad is None:
                first_grad = leaf_norms(gc)
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), w, w0))
    return {"losses": losses, "grad": first_grad, "change": change}


def leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(jnp.asarray(x, jnp.float32))))) for p, x in flat}


def readings(prog: dict, ref: dict) -> dict:
    """The three numbers compared, each the worst case:

    * ``loss``: largest |program - reference| / |reference| over the steps;
    * ``grad``: largest gap between the two norms of a leaf's first
      clipped gradient, over the larger of that leaf's reference norm and
      the median leaf's;
    * ``change``: the same for each leaf's norm of change after the steps.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of both leaf numbers.
    """
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    out = {"loss": max(gaps), "loss_at": f"step {int(np.argmax(gaps)) + 1}"}
    med_g = float(np.median(list(ref["grad"].values())))
    keep = [k for k, x in ref["grad"].items() if x >= 1e-3 * med_g]
    for name in ("grad", "change"):
        med = float(np.median([ref[name][k] for k in keep]))
        gap = {k: abs(prog[name][k] - ref[name][k]) / max(ref[name][k], med)
               for k in keep}
        out[f"{name}_at"] = max(gap, key=gap.get)
        out[name] = gap[out[f"{name}_at"]]
    out["leaves_left_out"] = len(ref["grad"]) - len(keep)
    return out
