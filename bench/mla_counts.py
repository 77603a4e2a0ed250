"""Operations and bytes of a latent-attention MoE model's serve steps
(DeepSeek-V2), counted at the published widths of its configuration file
from the work a step has to do, never from what the program moves.

Attention is counted per key in the per-head form whatever path computes
it: a score over nope + rope dims and a weighted sum over the value dims,
2 operations per multiply-add, per head and layer.  Matmul work counts the
parameters a token is multiplied by: every attention projection, the dense
MLP, the router, the top-k routed experts and the shared experts, and the
vocabulary head (decode only).
"""

from __future__ import annotations

import mla_serving

dims = mla_serving.dims


def attn_params(arch: dict) -> int:
    """One layer's attention matrices (q, kv_a, kv_b, o)."""
    m = dims(arch)
    return (m["d"] * m["H"] * (m["dn"] + m["dr"]) + m["d"] * (m["r"] + m["dr"])
            + m["r"] * m["H"] * (m["dn"] + m["dv"]) + m["H"] * m["dv"] * m["d"])


def expert_params(arch: dict) -> int:
    """One routed expert (gate, up, down)."""
    m = dims(arch)
    return 3 * m["d"] * m["f"]


def moe_layers(arch: dict) -> int:
    m = dims(arch)
    return m["L"] - m["k"]


def routed_params(arch: dict) -> int:
    """Every routed expert of every MoE layer."""
    return moe_layers(arch) * dims(arch)["E"] * expert_params(arch)


def active_params(arch: dict) -> int:
    """Matrix parameters one token is multiplied by below the head."""
    m = dims(arch)
    shared = 3 * m["d"] * m["ns"] * m["f"]
    per_moe = m["d"] * m["E"] + m["K"] * expert_params(arch) + shared
    return (m["L"] * attn_params(arch) + m["k"] * 3 * m["d"] * m["F"]
            + moe_layers(arch) * per_moe)


def attention_flops(arch: dict, keys: float) -> float:
    """``keys`` (query, key) pairs summed over queries, all layers."""
    m = dims(arch)
    return 2.0 * m["L"] * m["H"] * (m["dn"] + m["dr"] + m["dv"]) * keys


def decode_flops(arch: dict, lanes: int, context_tokens: int) -> float:
    """``lanes`` new tokens through every layer and the head, each
    attending to its whole context (``context_tokens`` sums the lanes'
    lengths, the new token's included)."""
    m = dims(arch)
    per_token = 2.0 * (active_params(arch) + m["d"] * m["V"])
    return lanes * per_token + attention_flops(arch, context_tokens)


def prefill_flops(arch: dict, new_tokens: int, past: int = 0) -> float:
    """``new_tokens`` prompt tokens after ``past`` held ones, causal; no
    head (the last prompt token is decoded by the first decode step)."""
    keys = new_tokens * past + new_tokens * (new_tokens + 1) / 2
    return 2.0 * active_params(arch) * new_tokens + attention_flops(arch, keys)


def non_expert_bytes(arch: dict, itemsize: int) -> int:
    """Weights a decode step reads whatever the routing: all but the
    routed experts and the embedding table (a step reads its lanes' rows)."""
    m = dims(arch)
    return (mla_serving.param_count(arch) - routed_params(arch)
            - m["V"] * m["d"]) * itemsize


def latent_row_bytes(arch: dict, itemsize: int) -> int:
    """Cache bytes of one token over all layers: one latent row each."""
    m = dims(arch)
    return m["L"] * (m["r"] + m["dr"]) * itemsize


def decode_bytes(arch: dict, steps: int, experts_hit: int,
                 context_tokens: int, itemsize: int) -> int:
    """Bytes ``steps`` decode steps need: the non-expert weights once a
    step, each expert hit (``experts_hit`` summed over steps and MoE
    layers) once, and the live latent rows of the active lanes."""
    return (steps * non_expert_bytes(arch, itemsize)
            + experts_hit * expert_params(arch) * itemsize
            + context_tokens * latent_row_bytes(arch, itemsize))
