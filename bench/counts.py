"""Operations and bytes a step needs, computed from shapes, and the peaks
they are divided by.

Everything here is counted at the published widths in a configuration file
(unpadded heads and vocabulary), from the work a step has to do, never from
what the program happens to move.  A later change that does the same work
with fewer bytes or operations therefore reads a higher share of the peak.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add a row with its source")
    return table[device_kind]


def dims(arch: dict) -> dict:
    """Published sizes of a decoder-only transformer, from a configuration
    file's Hugging Face keys."""
    d = arch["hidden_size"]
    h = arch["num_attention_heads"]
    return {"d": d, "h": h, "kv": arch["num_key_value_heads"],
            "hd": arch.get("head_dim", d // h),
            "f": arch["intermediate_size"], "L": arch["num_hidden_layers"],
            "V": arch["vocab_size"], "bias": bool(arch.get("qkv_bias", False)),
            "tied": bool(arch["tie_word_embeddings"])}


def layer_matmul_params(arch: dict) -> int:
    """Weights one layer multiplies by: q, k, v, o projections and the
    gated MLP (norms and biases are not matrix work)."""
    m = dims(arch)
    attn = m["d"] * m["hd"] * (2 * m["h"] + 2 * m["kv"])
    return attn + 3 * m["d"] * m["f"]


def param_count(arch: dict) -> int:
    """Every parameter of the published model (tied embeddings once)."""
    m = dims(arch)
    per_layer = layer_matmul_params(arch) + 2 * m["d"]
    if m["bias"]:
        per_layer += m["hd"] * (m["h"] + 2 * m["kv"])
    head = 0 if m["tied"] else m["V"] * m["d"]
    return m["V"] * m["d"] + m["L"] * per_layer + m["d"] + head


def attention_flops(arch: dict, queries: int, context: int) -> float:
    """Scores and weighted sum of ``queries`` tokens against ``context``
    keys each, all layers: 2 multiply-adds per head dimension per key."""
    m = dims(arch)
    return 4.0 * m["L"] * m["h"] * m["hd"] * queries * context


def decode_flops(arch: dict, lanes: int, context_tokens: int) -> float:
    """Decode steps of ``lanes`` new tokens in all: each through every
    layer and the vocabulary head, attending to its whole context
    (``context_tokens`` sums each lane's length including the new token)."""
    m = dims(arch)
    per_token = 2.0 * (m["L"] * layer_matmul_params(arch) + m["d"] * m["V"])
    return lanes * per_token + attention_flops(arch, 1, context_tokens)


def prefill_flops(arch: dict, new_tokens: int, past: int = 0) -> float:
    """``new_tokens`` prompt tokens after ``past`` tokens whose keys and
    values already exist, causal; no vocabulary head (the last prompt token
    is decoded by the first decode step)."""
    m = dims(arch)
    dense = 2.0 * m["L"] * layer_matmul_params(arch) * new_tokens
    # token i (0-based among the new ones) attends to past + i + 1 keys
    keys = new_tokens * past + new_tokens * (new_tokens + 1) / 2
    return dense + 4.0 * m["L"] * m["h"] * m["hd"] * keys


def train_flops(arch: dict, tokens: int) -> float:
    """6 x parameters x tokens: forward and backward of every weight."""
    return 6.0 * param_count(arch) * tokens


def weight_bytes(arch: dict, itemsize: int) -> int:
    """Bytes of every weight a decode step reads once (embedding rows of
    the batch are a few rows; a tied head reads the whole matrix)."""
    return param_count(arch) * itemsize


def kv_bytes_per_token(arch: dict, itemsize: int) -> int:
    m = dims(arch)
    return 2 * m["L"] * m["kv"] * m["hd"] * itemsize


def decode_bytes(arch: dict, steps: int, context_tokens: int,
                 itemsize: int) -> int:
    """Bytes ``steps`` decode steps need: the weights once per step, and
    each active lane's live keys and values (``context_tokens`` in all,
    the new token's included)."""
    return (steps * weight_bytes(arch, itemsize)
            + context_tokens * kv_bytes_per_token(arch, itemsize))


def leaf_pages(shapes, page_size: int, itemsize: int = 4) -> int:
    """Pages of a page-aligned packing of arrays of ``shapes``."""
    return sum(max(1, -(-math.prod(s) * itemsize // page_size)) for s in shapes)


def page_copy_bytes(pages: int, page_size: int) -> int:
    """A page copy reads and writes each page once."""
    return 2 * pages * page_size
