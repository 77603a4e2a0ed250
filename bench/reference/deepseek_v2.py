"""DeepSeek-V2 as published (arXiv:2405.04434 §2.1-2.2; Hugging Face
modeling_deepseek): multi-head latent attention without q LoRA, DeepSeekMoE
with shared experts, RMSNorm, RoPE with DeepSeek's yarn.  float32, every
matmul at ``Precision.HIGHEST`` and under
``jax.default_matmul_precision("highest")``; no kernels, no cache.

Per layer: q = x Wq split into (nope, rope) per head; [c, k_pe] = x Wkv_a,
c normalised (kv_a_layernorm); [k_nope, v] = c Wkv_b per head; q_rope and
k_pe (one, shared by the heads) are rotated as published, interleaved
pairs (x_2i, x_2i+1) at angle pos / inv_freq_i; scores (q_nope . k_nope +
q_rope . k_pe) x mscale^2 / sqrt(nope + rope), causal softmax.  The MLP is
dense for the first ``first_k_dense_replace`` layers, then a float32
softmax router over the routed experts, greedy top-k (not renormalised
unless ``norm_topk_prob``), times ``routed_scaling_factor``, plus the shared
experts as one SwiGLU.

Departures, none of them in the mathematics: attention runs in blocks of
queries and the MoE in blocks of tokens, so that a 14k-token sequence fits
in memory; each block evaluates every routed expert and weights it by the
router's top-k weights (zero for the others), which equals computing the
chosen experts only.  The layer weights are cast to float32 one layer at a
time.  ``numerics="fp8"`` is the control: every matmul operand (the router
and attention's included) rounded to float8 e4m3, as in ``llm.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.llm import _fp8, _mm

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
T_BLOCK = 256


def _dims(arch: dict) -> dict:
    return {"H": arch["num_attention_heads"], "r": arch["kv_lora_rank"],
            "dn": arch["qk_nope_head_dim"], "dr": arch["qk_rope_head_dim"],
            "dv": arch["v_head_dim"], "K": arch["num_experts_per_tok"]}


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(arch: dict) -> np.ndarray:
    """Inverse frequencies of the rope dims (modeling_deepseek
    ``DeepseekV2YarnRotaryEmbedding``), in float64: the original ones
    below the ramp, those divided by ``factor`` above it."""
    y = dict(arch["rope_scaling"])
    dim, base = arch["qk_rope_head_dim"], float(arch["rope_theta"])
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low or 1e-3), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def softmax_scale(arch: dict) -> float:
    y = dict(arch["rope_scaling"])
    m = _mscale(y["factor"], y["mscale_all_dim"])
    return m * m / math.sqrt(arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"])


def _rope(x, inv, cs):
    """x [S, ..., dr] with interleaved pairs rotated at positions 0..S-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    c, s = (jnp.cos(ang) * cs).reshape(shape), (jnp.sin(ang) * cs).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, g, u, dn, numerics):
    return _mm(jax.nn.silu(_mm(x, g, numerics, "sd,df->sf"))
               * _mm(x, u, numerics, "sd,df->sf"), dn, numerics, "sf,fd->sd")


def _attention(arch, numerics, x, p):
    """x [S, d] (normed) -> attention output [S, d]."""
    m = _dims(arch)
    H, r, dn, dr, dv = m["H"], m["r"], m["dn"], m["dr"], m["dv"]
    S = x.shape[0]
    y = dict(arch["rope_scaling"])
    inv = jnp.asarray(yarn_inv_freq(arch), jnp.float32)
    cs = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                     y["mscale_all_dim"])
    q = _mm(x, p["q_proj"], numerics, "sd,de->se").reshape(S, H, dn + dr)
    kva = _mm(x, p["kv_a_proj_with_mqa"], numerics, "sd,de->se")
    c = _rms(kva[:, :r], p["kv_a_layernorm"], arch["rms_norm_eps"])
    k_pe = _rope(kva[:, r:], inv, cs)                       # [S, dr]
    q_pe = _rope(q[..., dn:], inv, cs)                      # [S, H, dr]
    kv = _mm(c, p["kv_b_proj"], numerics, "sr,re->se").reshape(S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope = q[..., :dn]
    if numerics == "fp8":
        q_nope, q_pe, k_nope, k_pe, v = (_fp8(a, -1) for a in
                                         (q_nope, q_pe, k_nope, k_pe, v))
    scale = softmax_scale(arch)
    keys = jnp.arange(S)

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * Q_BLOCK, Q_BLOCK)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * Q_BLOCK, Q_BLOCK)
        sc = (jnp.einsum("qhn,khn->hqk", qn, k_nope, precision=HIGHEST)
              + jnp.einsum("qhr,kr->hqk", qp, k_pe, precision=HIGHEST)) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        if numerics == "fp8":
            pr = _fp8(pr, -1)
        return jnp.einsum("hqk,khv->qhv", pr, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * dv)
    return _mm(o, p["o_proj"], numerics, "se,ed->sd")


def _moe(arch, numerics, x, p):
    """x [S, d] (normed) -> (routed plus shared experts [S, d], each token's
    routing margin [S]: its k-th router probability less its (k+1)-th)."""
    K = arch["num_experts_per_tok"]
    ex = p["experts"]

    def block(xb):
        logits = _mm(xb, p["gate"], numerics, "td,de->te")
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, K)
        margin = top_w[:, -1] - jax.lax.top_k(probs, K + 1)[0][:, -1]
        if arch["norm_topk_prob"]:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        top_w = top_w * arch["routed_scaling_factor"]
        wts = jnp.zeros_like(probs).at[jnp.arange(xb.shape[0])[:, None],
                                       top_i].set(top_w)      # [t, E]
        h = jax.nn.silu(_mm(xb, ex["gate_proj"], numerics, "td,edf->tef")) \
            * _mm(xb, ex["up_proj"], numerics, "td,edf->tef")
        return _mm(h * wts[..., None], ex["down_proj"], numerics,
                   "tef,efd->td"), margin

    S, d = x.shape
    routed, margin = jax.lax.map(block, x.reshape(S // T_BLOCK, T_BLOCK, d))
    sh = p["shared_experts"]
    return routed.reshape(S, d) + _swiglu(
        x, sh["gate_proj"], sh["up_proj"], sh["down_proj"],
        numerics), margin.reshape(S)


def _layer(arch, numerics, mlp, x, p):
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["rms_norm_eps"]
    lay = f32["layers"]
    x = x + _attention(arch, numerics, _rms(x, lay["input_layernorm"], eps), lay)
    y = _rms(x, lay["post_attention_layernorm"], eps)
    if mlp == "dense":
        m = f32["dense_mlp"]
        return x + _swiglu(y, m["gate_proj"], m["up_proj"], m["down_proj"],
                           numerics), jnp.full(x.shape[:1], jnp.inf)
    y, margin = _moe(arch, numerics, y, f32["moe"])
    return x + y, margin


def hidden(arch: dict, w: dict, tokens: jax.Array, numerics: str = "f32",
           margins: bool = False):
    """Final-normed hidden states [S, d] of ``tokens`` [S] (S a multiple of
    ``Q_BLOCK``, hence of ``T_BLOCK``); with ``margins`` also each token's
    least routing margin over the MoE layers [S], where a small margin
    means a near-tie that a lower precision can flip."""
    k = arch["first_k_dense_replace"]
    x = jnp.take(w["embed_tokens"], tokens, axis=0).astype(jnp.float32)
    lay = w["layers"]
    dense = {"layers": jax.tree.map(lambda a: a[:k], lay),
             "dense_mlp": w["dense_mlp"]}
    moe = {"layers": jax.tree.map(lambda a: a[k:], lay), "moe": w["moe"]}
    least = jnp.full(tokens.shape, jnp.inf)
    for kind, group in (("dense", dense), ("moe", moe)):
        body = partial(_layer, arch, numerics, kind)
        x, m = jax.lax.scan(body, x, group)
        least = jnp.minimum(least, m.min(0))
    h = _rms(x, w["norm"].astype(jnp.float32), arch["rms_norm_eps"])
    return (h, least) if margins else h


@partial(jax.jit, static_argnums=(0, 4))
def _gaps(arch_items, w, tokens, at_and_tok, numerics):
    """As ``llm._gaps``: at positions ``at``, how far the served token, and
    the control's first choice, lie below the reference's best, in logit
    standard deviations."""
    arch = dict(arch_items)
    at, tok = at_and_tok
    h = hidden(arch, w, tokens)
    hc = hidden(arch, w, tokens, "fp8") if numerics == "fp8" else None

    def chunk(args):
        a, t = args
        head = w["lm_head"].astype(jnp.float32)
        ref = _mm(h[a], head, "f32", "sd,dv->sv")
        top, spread = ref.max(-1), ref.std(-1)
        served = jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
        out = [(top - served) / spread]
        if hc is not None:
            pick = _mm(hc[a], head, "fp8", "sd,dv->sv").argmax(-1)
            ctl = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            out.append((top - ctl) / spread)
        return out

    return [x.reshape(-1) for x in
            jax.lax.map(chunk, (at.reshape(-1, 256), tok.reshape(-1, 256)))]


@partial(jax.jit, static_argnums=0)
def _margins(arch_items, w, tokens):
    return hidden(dict(arch_items), w, tokens, margins=True)[1]


def routing_margins(arch: dict, w: dict, prompt, served, width: int):
    """Least routing margin over the MoE layers at the position each
    served token was chosen from (as ``served_gaps`` orders them)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    tokens = np.zeros(width, np.int32)
    tokens[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        m = _margins(tuple(sorted(arch.items())), w, jnp.asarray(tokens))
    return np.asarray(m, np.float64)[len(prompt) - 1:len(seq)]


def served_gaps(arch: dict, w: dict, prompt, served, width: int,
                max_served: int, control: bool = False):
    """``llm.served_gaps`` for this architecture: gaps (in logit spreads)
    at every served token of one request, the sequence right-padded to
    ``width`` tokens and the positions to a multiple of 256 of at least
    ``max_served``, so that every request of a cell runs one program."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    n, k = len(seq), len(served)
    if n > width or width % Q_BLOCK:
        raise ValueError(f"{n} tokens at width {width}; the width must be "
                         f"a multiple of {Q_BLOCK} at least as long")
    tokens = np.zeros(width, np.int32)
    tokens[:n] = seq
    slots = -(-max(k, max_served) // 256) * 256
    at = np.zeros(slots, np.int32)
    at[:k] = np.arange(len(prompt) - 1, n, dtype=np.int32)
    tok = np.zeros(slots, np.int32)
    tok[:k] = np.asarray(served, np.int32)
    with jax.default_matmul_precision("highest"):
        out = _gaps(tuple(sorted(arch.items())), w, jnp.asarray(tokens),
                    (jnp.asarray(at), jnp.asarray(tok)),
                    "fp8" if control else "f32")
    return [np.asarray(x, np.float64)[:k] for x in out]
