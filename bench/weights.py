"""Weights made from the seed, on the device, in one jitted call.

The layout is the benchmark's own, named after the published checkpoint's
tensors and stacked over layers; a driver maps it to the program's layout.
The plain references read this layout, so neither side reads the other's
arrays: each makes its copy from the same seed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from counts import dims


def key_for(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any whole-number seed (64 bits are kept)."""
    words = np.random.SeedSequence([int(seed) % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def shapes(arch: dict) -> dict:
    """Leaf shapes of the benchmark layout."""
    m = dims(arch)
    L, d, f = m["L"], m["d"], m["f"]
    q, kv = m["h"] * m["hd"], m["kv"] * m["hd"]
    layers = {"ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "ln2": (L, d),
              "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    if m["bias"]:
        layers.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    out = {"embed": (m["V"], d), "layers": layers, "final_norm": (d,)}
    if not m["tied"]:
        out["lm_head"] = (d, m["V"])
    return out


def _init(key, name: str, shape: tuple, dtype) -> jax.Array:
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("embed", "lm_head", "bq", "bk", "bv"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:   # [.., fan_in, fan_out]
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    return x.astype(dtype)


def make(arch: dict, seed: int, dtype) -> dict:
    """All weights of ``arch`` from ``seed`` in ``dtype`` on the device."""
    tree = shapes(arch)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = [path[-1].key for path, _ in flat]
    leaf_shapes = [s for _, s in flat]

    def build(key):
        keys = jax.random.split(key, len(leaf_shapes))
        return [_init(k, n, s, dtype) for k, n, s in zip(keys, names, leaf_shapes)]

    out = jax.jit(build)(key_for(seed))
    return jax.tree_util.tree_unflatten(treedef, out)
