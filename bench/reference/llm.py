"""A decoder-only transformer as published for Qwen2 and Llama-style models
(SmolLM): RMSNorm, rotary embeddings (half rotation), grouped-query causal
attention with optional q/k/v bias, SwiGLU MLP, tied or separate vocabulary
head.  float32 with every matmul at ``Precision.HIGHEST``.

``numerics="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 (a scale per weight matrix, per token row of activations),
the step below the bfloat16 the configurations serve in.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from counts import dims

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis) -> jax.Array:
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, numerics: str, spec: str):
    """einsum with operands rounded as ``numerics`` says (activations per
    row over their last axis, weights per matrix)."""
    if numerics == "fp8":
        a = _fp8(a, -1)
        b = _fp8(b, None)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, D]; positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    c, sn = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _layer(arch, numerics, x, p):
    m = dims(arch)
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    b, s, _ = x.shape
    h, kv, hd = m["h"], m["kv"], m["hd"]
    y = _rms(x, p["ln1"], eps)
    q = _mm(y, p["wq"], numerics, "bsd,de->bse")
    k = _mm(y, p["wk"], numerics, "bsd,de->bse")
    v = _mm(y, p["wv"], numerics, "bsd,de->bse")
    if m["bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(b, s, h, hd), theta)
    k = _rope(k.reshape(b, s, kv, hd), theta)
    v = v.reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)          # head i reads kv head i // (h/kv)
    if numerics == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    if numerics == "fp8":
        pr = _fp8(pr, -1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", pr, v, precision=HIGHEST)
    x = x + _mm(o.reshape(b, s, h * hd), p["wo"], numerics, "bse,ed->bsd")
    y = _rms(x, p["ln2"], eps)
    g = _mm(y, p["w_gate"], numerics, "bsd,df->bsf")
    u = _mm(y, p["w_up"], numerics, "bsd,df->bsf")
    return x + _mm(jax.nn.silu(g) * u, p["w_down"], numerics, "bsf,fd->bsd")


def hidden(arch: dict, w: dict, tokens: jax.Array, numerics: str = "f32",
           remat: bool = False) -> jax.Array:
    """Final-normed hidden states [B, S, d] of ``tokens`` [B, S]."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = jnp.take(w["embed"], tokens, axis=0)
    body = partial(_layer, arch, numerics)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, w["layers"])
    return _rms(x, w["final_norm"], arch["rms_norm_eps"])


def logits(arch: dict, w: dict, h: jax.Array, numerics: str = "f32"):
    """Vocabulary logits of hidden rows ``h`` [..., d]."""
    head = (w["embed"].T if arch["tie_word_embeddings"] else w["lm_head"])
    return _mm(h, head.astype(jnp.float32), numerics, "...d,dv->...v")


@partial(jax.jit, static_argnums=(0, 4))
def _gaps(arch_items, w, tokens, at_and_tok, numerics):
    """Reference logits at positions ``at`` of ``tokens`` [1, S]: how far
    the served token, and the control's first choice, lie below the
    reference's best, in units of the logits' standard deviation.
    Positions are taken 256 at a time so the logits stay small."""
    arch = dict(arch_items)
    at, tok = at_and_tok
    h = hidden(arch, w, tokens)[0]
    hc = hidden(arch, w, tokens, "fp8")[0] if numerics == "fp8" else None

    def chunk(args):
        a, t = args
        ref = logits(arch, w, h[a])
        top = ref.max(-1)
        spread = ref.std(-1)
        served = jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
        out = [(top - served) / spread]
        if hc is not None:
            pick = logits(arch, w, hc[a], "fp8").argmax(-1)
            ctl = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            out.append((top - ctl) / spread)
        return out

    return [x.reshape(-1) for x in
            jax.lax.map(chunk, (at.reshape(-1, 256), tok.reshape(-1, 256)))]


def served_gaps(arch: dict, w: dict, prompt, served, width: int,
                max_served: int, control: bool = False):
    """Gaps (in logit spreads) at every served token of one request:
    token i of ``served`` is the model's choice after ``prompt`` and
    ``served[:i]``.  With ``control``, also the gaps of the fp8 control's
    own first choices at the same positions.  The sequence is right-padded
    to ``width`` tokens (harmless under causal attention) and the positions
    to a multiple of 256 of at least ``max_served``, so that every request
    of a cell runs the same compiled program."""
    import numpy as np
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    n, k = len(seq), len(served)
    if n > width:
        raise ValueError(f"a sequence of {n} tokens exceeds width {width}")
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :n] = seq
    slots = -(-max(k, max_served) // 256) * 256
    at = np.zeros(slots, np.int32)
    at[:k] = np.arange(len(prompt) - 1, n, dtype=np.int32)
    tok = np.zeros(slots, np.int32)
    tok[:k] = np.asarray(served, np.int32)
    out = _gaps(tuple(sorted(arch.items())), w, jnp.asarray(tokens),
                (jnp.asarray(at), jnp.asarray(tok)),
                "fp8" if control else "f32")
    return [np.asarray(x, np.float64)[:k] for x in out]
