"""What a latent-attention MoE configuration (DeepSeek-V2) brings to the
serve drivers: its published sizes, the program's configuration, weights
from the seed in the benchmark's own layout, and the mapping into the
program's parameter tree.

The layout is named after the published checkpoint's tensors, every
matrix [in, out], stacked over layers: attention and norms over all
layers, the MLP over the leading dense layers, the router and experts over
the MoE layers.  ``reference/deepseek_v2.py`` reads this layout, and the
program gets it through ``to_program``; neither reads the other's arrays.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from weights import key_for

ARCH_KEYS = ("hidden_size", "num_attention_heads", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers", "vocab_size",
             "tie_word_embeddings", "rms_norm_eps", "rope_theta",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_routed_experts", "n_shared_experts",
             "num_experts_per_tok", "first_k_dense_replace",
             "norm_topk_prob", "routed_scaling_factor", "rope_scaling")

NORMS = ("input_layernorm", "post_attention_layernorm", "kv_a_layernorm",
         "norm")


def arch_of(model: dict) -> dict:
    """The published sizes of a configuration file (rope_scaling as a
    sorted tuple of pairs, so that the dict can key a compiled program)."""
    out = {k: model[k] for k in ARCH_KEYS}
    out["rope_scaling"] = tuple(sorted(model["rope_scaling"].items()))
    return out


def dims(arch: dict) -> dict:
    return {"d": arch["hidden_size"], "H": arch["num_attention_heads"],
            "r": arch["kv_lora_rank"], "dn": arch["qk_nope_head_dim"],
            "dr": arch["qk_rope_head_dim"], "dv": arch["v_head_dim"],
            "F": arch["intermediate_size"], "f": arch["moe_intermediate_size"],
            "E": arch["n_routed_experts"], "K": arch["num_experts_per_tok"],
            "ns": arch["n_shared_experts"], "L": arch["num_hidden_layers"],
            "k": arch["first_k_dense_replace"], "V": arch["vocab_size"]}


def yarn_of(arch: dict) -> tuple:
    y = dict(arch["rope_scaling"])
    if y["type"] != "yarn":
        raise ValueError(f"rope_scaling {y['type']!r}: only yarn is supported")
    return (float(y["factor"]), int(y["original_max_position_embeddings"]),
            float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
            float(y["mscale_all_dim"]))


def program_config(model: dict):
    """The program's ``ModelConfig`` from the file's published keys, its
    dtypes and its ``program.model`` settings."""
    from repro.configs.base import ModelConfig
    a = arch_of(model)
    m = dims(a)
    if model.get("q_lora_rank") is not None:
        raise ValueError("q LoRA (q_lora_rank) is not supported")
    return ModelConfig(
        name=model["name"], family="moe", num_layers=m["L"], d_model=m["d"],
        num_heads=m["H"], num_kv_heads=m["H"], head_dim=m["dn"] + m["dr"],
        d_ff=m["F"], vocab_size=m["V"], num_experts=m["E"], top_k=m["K"],
        moe_d_ff=m["f"], num_shared_experts=m["ns"], first_k_dense=m["k"],
        norm_topk_prob=a["norm_topk_prob"],
        routed_scaling_factor=float(a["routed_scaling_factor"]),
        kv_lora_rank=m["r"], qk_nope_head_dim=m["dn"],
        qk_rope_head_dim=m["dr"], v_head_dim=m["dv"],
        rope_theta=float(a["rope_theta"]), rope_yarn=yarn_of(a),
        tie_embeddings=a["tie_word_embeddings"], norm_eps=a["rms_norm_eps"],
        act="silu", param_dtype=model["dtypes"]["weights"],
        compute_dtype=model["dtypes"]["compute"], **model["program"]["model"])


def shapes(arch: dict) -> dict:
    """Leaf shapes of the benchmark layout."""
    m = dims(arch)
    d, H, r, L, k = m["d"], m["H"], m["r"], m["L"], m["k"]
    n = L - k
    fs = m["ns"] * m["f"]
    return {
        "embed_tokens": (m["V"], d), "norm": (d,), "lm_head": (d, m["V"]),
        "layers": {
            "input_layernorm": (L, d), "post_attention_layernorm": (L, d),
            "q_proj": (L, d, H * (m["dn"] + m["dr"])),
            "kv_a_proj_with_mqa": (L, d, r + m["dr"]),
            "kv_a_layernorm": (L, r),
            "kv_b_proj": (L, r, H * (m["dn"] + m["dv"])),
            "o_proj": (L, H * m["dv"], d)},
        "dense_mlp": {"gate_proj": (k, d, m["F"]), "up_proj": (k, d, m["F"]),
                      "down_proj": (k, m["F"], d)},
        "moe": {"gate": (n, d, m["E"]),
                "experts": {"gate_proj": (n, m["E"], d, m["f"]),
                            "up_proj": (n, m["E"], d, m["f"]),
                            "down_proj": (n, m["E"], m["f"], d)},
                "shared_experts": {"gate_proj": (n, d, fs),
                                   "up_proj": (n, d, fs),
                                   "down_proj": (n, fs, d)}},
    }


def param_count(arch: dict) -> int:
    flat = jax.tree.leaves(shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(s) for s in flat)


def _init(key, name: str, shape: tuple, dtype) -> jax.Array:
    if name in NORMS:
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("embed_tokens", "lm_head"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:   # [.., fan_in, fan_out]
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    return x.astype(dtype)


def make(arch: dict, seed: int, dtype) -> dict:
    """All weights of ``arch`` from ``seed`` in ``dtype`` on the device,
    one jitted call per leaf (so that no leaf's float32 draw lives beside
    another's)."""
    tree = shapes(arch)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key_for(seed), len(flat))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        out.append(jax.jit(_init, static_argnums=(1, 2, 3))(
            keys[i], name, shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def rope_permutation(arch: dict) -> np.ndarray:
    """Column order that turns the published interleaved rope pairs
    (x0, x1), (x2, x3), ... into the program's half-rotation layout
    (evens, then odds).  q and k are permuted alike, so every score is
    unchanged."""
    dr = arch["qk_rope_head_dim"]
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def to_program(arch: dict, w: dict) -> dict:
    """The benchmark layout in the program's parameter tree, consuming
    ``w``: the large leaves are handed over as they are, and only the
    rope columns of q_proj and kv_a_proj_with_mqa are permuted."""
    m = dims(arch)
    perm = rope_permutation(arch)
    H, dn, dr, r, k = m["H"], m["dn"], m["dr"], m["r"], m["k"]
    q_cols = np.concatenate([h * (dn + dr) + np.concatenate(
        [np.arange(dn), dn + perm]) for h in range(H)])
    kv_cols = np.concatenate([np.arange(r), r + perm])
    lay = w.pop("layers")

    @jax.jit
    def attn(lay):
        return {"wq": lay["q_proj"][..., q_cols],
                "wkv_a": lay["kv_a_proj_with_mqa"][..., kv_cols],
                "kv_norm": lay["kv_a_layernorm"], "wkv_b": lay["kv_b_proj"],
                "wo": lay["o_proj"]}

    a = attn(lay)

    def part(tree, lo, hi):
        return jax.tree.map(lambda x: x[lo:hi], tree)

    def seg(lo, hi):
        return {"ln1": lay["input_layernorm"][lo:hi], "attn": part(a, lo, hi),
                "ln2": lay["post_attention_layernorm"][lo:hi]}

    dense, moe = w.pop("dense_mlp"), w.pop("moe")
    segments = [dict(seg(0, k), mlp={"w_gate": dense["gate_proj"],
                                     "w_up": dense["up_proj"],
                                     "w_down": dense["down_proj"]}),
                dict(seg(k, m["L"]),
                     moe={"router": moe["gate"],
                          "w_gate": moe["experts"]["gate_proj"],
                          "w_up": moe["experts"]["up_proj"],
                          "w_down": moe["experts"]["down_proj"]},
                     shared={"w_gate": moe["shared_experts"]["gate_proj"],
                             "w_up": moe["shared_experts"]["up_proj"],
                             "w_down": moe["shared_experts"]["down_proj"]})]
    return {"embed": w.pop("embed_tokens"), "segments": segments,
            "final_norm": w.pop("norm"), "lm_head": w.pop("lm_head")}
