"""Core transformer layers: norms, RoPE (+M-RoPE), GQA attention, MLPs.

Attention implementations (selected by ``impl`` / sequence size):

  dense        full [Sq, Sk] score matrix + mask — exact baseline, fine for
               short sequences and the smoke tests.
  chunked      online-softmax scan over KV chunks (flash-attention recurrence
               in pure JAX) — memory O(Sq · chunk); what the 32k dry-runs
               lower.  ``causal_skip=True`` additionally skips fully-masked
               KV chunks per Q chunk (triangular schedule: ~2× FLOP saving
               for causal, window/Sk saving for sliding window) — this is a
               §Perf hillclimb lever.
  (pallas)     kernels/flash_attention — drop-in on real TPU; validated in
               interpret mode by tests, not lowered in the CPU dry-run.

All softmax/normalizer math runs in fp32 regardless of compute dtype.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import with_logical_constraint as wlc

NEG_INF = -2.0e38  # fp32-safe mask value (avoid inf arithmetic -> NaN)


# --------------------------------------------------------------------- norms


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------- RoPE


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for half-rotation RoPE: [head_dim // 2], fp32."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """DeepSeek yarn's attention magnitude: 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, yarn) -> jax.Array:
    """DeepSeek yarn inverse frequencies [dim // 2] (modeling_deepseek
    ``DeepseekV2YarnRotaryEmbedding``): the original frequencies below
    rotary dim ``low``, those divided by ``factor`` above ``high``, a linear
    ramp between; ``low``/``high`` are where a frequency turns ``beta_fast``
    and ``beta_slow`` times over the original context."""
    factor, orig, beta_fast, beta_slow, mscale, mscale_all_dim = yarn
    if mscale != mscale_all_dim:
        raise ValueError("yarn with mscale != mscale_all_dim scales cos and "
                         "sin, which is not implemented")

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[Tuple[int, int, int]] = None,
               yarn=None) -> jax.Array:
    """Rotary embedding (LLaMA half-rotation layout).

    x:         [..., S, H, D]
    positions: [B, S] int — or [B, 3, S] for M-RoPE (temporal/height/width
               position triplets; Qwen2-VL §2).  ``mrope_sections`` gives the
               number of *frequency pairs* driven by each component; they must
               sum to D // 2.
    yarn:      DeepSeek yarn parameters (``ModelConfig.rope_yarn``) with
               mscale == mscale_all_dim, as published, so that cos and sin
               keep their scale of 1.
    """
    d2 = x.shape[-1] // 2
    inv = (rope_freqs(x.shape[-1], theta) if yarn is None
           else yarn_freqs(x.shape[-1], theta, yarn))  # [d2]
    if mrope_sections is None:
        pos = positions.astype(jnp.float32)  # [B, S]
        angles = pos[..., None] * inv  # [B, S, d2]
    else:
        assert positions.ndim == 3 and positions.shape[1] == 3, positions.shape
        assert sum(mrope_sections) == d2, (mrope_sections, d2)
        pos = positions.astype(jnp.float32)  # [B, 3, S]
        comp = jnp.repeat(
            jnp.arange(3), jnp.array(mrope_sections), total_repeat_length=d2
        )  # [d2] -> which position component drives each freq pair
        pos_per_freq = jnp.take_along_axis(
            pos, comp[None, :, None].repeat(pos.shape[0], 0), axis=1
        )  # [B, d2, S]
        angles = jnp.swapaxes(pos_per_freq, 1, 2) * inv  # [B, S, d2]
    cos = jnp.cos(angles)[..., None, :]  # [B, S, 1, d2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ attention


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static attention behavior for one call."""

    causal: bool = True
    window: Optional[int] = None       # sliding-window size (None = full)
    impl: str = "auto"                 # "dense" | "chunked" | "auto"
    chunk_size: int = 512
    causal_skip: bool = False          # triangular chunk schedule (perf lever)
    scale: Optional[float] = None      # softmax scale; None = 1/sqrt(D)

    def scale_for(self, d: int) -> float:
        return self.scale if self.scale is not None else 1.0 / math.sqrt(d)


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """[B,S,H,D] -> [B,S,KVH,rep,D] grouped for GQA einsums."""
    b, s, h, d = q.shape
    assert h % n_kv == 0, (h, n_kv)
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, spec: AttnSpec,
               k_valid: Optional[jax.Array] = None) -> jax.Array:
    """[B, Sq, Sk] fp32 additive bias from positions (+ validity)."""
    d = q_pos[:, :, None] - k_pos[:, None, :]
    ok = jnp.ones(d.shape, bool)
    if spec.causal:
        ok &= d >= 0
    if spec.window is not None:
        ok &= d < spec.window
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention(
    q: jax.Array,                 # [B, Sq, H, D]
    k: jax.Array,                 # [B, Sk, KVH, D]
    v: jax.Array,                 # [B, Sk, KVH, D]
    spec: AttnSpec,
    q_positions: jax.Array,       # [B, Sq] int32
    k_positions: jax.Array,       # [B, Sk] int32
    k_valid: Optional[jax.Array] = None,   # [B, Sk] bool (cache validity)
) -> jax.Array:
    """GQA attention -> [B, Sq, H, D].  Softmax in fp32."""
    impl = spec.impl
    if impl == "auto":
        impl = "chunked" if q.shape[1] * k.shape[1] > 1024 * 1024 else "dense"
    if impl == "dense":
        return _dense_attention(q, k, v, spec, q_positions, k_positions, k_valid)
    return _chunked_attention(q, k, v, spec, q_positions, k_positions, k_valid)


def _dense_attention(q, k, v, spec, q_pos, k_pos, k_valid):
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    qg = _group(q, kvh)                                   # [B,Sq,KVH,rep,D]
    scale = spec.scale_for(d)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale         # [B,KVH,rep,Sq,Sk]
    s = s + _mask_bias(q_pos, k_pos, spec, k_valid)[:, None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _flash_vjp_attention(q, k, v, spec, q_pos, k_pos, k_valid):
    """Chunked attention with a flash-style custom VJP (§Perf H3 iter-3).

    The default AD of the online-softmax scan saves the fp32 probability
    tensor of every KV chunk for the backward — O(Sq·Sk) residuals that
    dominate the training memory term.  This VJP saves only (o, m, l)
    (O(Sq) per head) and *recomputes* probabilities chunk-by-chunk in the
    backward, exactly the FlashAttention recurrence:

        D    = rowsum(do ⊙ o)
        P_c  = exp(s_c − m) / l
        ds_c = P_c ⊙ (do·v_cᵀ − D)
        dq  += ds_c·k_c·scale;  dk_c = ds_cᵀ·q·scale;  dv_c = P_cᵀ·do
    """
    import numpy as _np

    @jax.custom_vjp
    def f(q, k, v, q_pos, k_pos, k_valid):
        o, _, _ = _chunked_forward(q, k, v, spec, q_pos, k_pos, k_valid)
        return o

    def f_fwd(q, k, v, q_pos, k_pos, k_valid):
        o, m, l = _chunked_forward(q, k, v, spec, q_pos, k_pos, k_valid)
        return o, (q, k, v, q_pos, k_pos, k_valid, o, m, l)

    def f_bwd(res, do):
        q, k, v, q_pos, k_pos, k_valid, o, m, l = res
        dq, dk, dv = _chunked_backward(q, k, v, spec, q_pos, k_pos, k_valid,
                                       o, m, l, do)
        zi = lambda a: _np.zeros(a.shape, jax.dtypes.float0)
        return (dq, dk, dv, zi(q_pos), zi(k_pos),
                zi(k_valid) if k_valid is not None else None)

    f.defvjp(f_fwd, f_bwd)
    return f(q, k, v, q_pos, k_pos, k_valid)


def _pad_chunks(q, k, v, spec, q_pos, k_pos, k_valid):
    b, sq = q.shape[0], q.shape[1]
    sk = k.shape[1]
    ck = min(spec.chunk_size, sk)
    if sk % ck != 0:
        pad = ck - sk % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2**30)
        kval = k_valid if k_valid is not None else jnp.ones((b, sk), bool)
        k_valid = jnp.pad(kval, ((0, 0), (0, pad)), constant_values=False)
    elif k_valid is None:
        k_valid = jnp.ones((b, k.shape[1]), bool)
    return k, v, k_pos, k_valid, ck


def _chunked_forward(q, k, v, spec, q_pos, k_pos, k_valid):
    """Shared scan: returns (o [B,Sq,H,D], m, l [B,g,r,Sq] fp32)."""
    b, sq, h, d = q.shape
    kvh, dv = k.shape[2], v.shape[-1]
    k, v, k_pos, k_valid, ck = _pad_chunks(q, k, v, spec, q_pos, k_pos, k_valid)
    sk = k.shape[1]
    n_chunks = sk // ck
    qg = _group(q, kvh).astype(jnp.float32)
    scale = spec.scale_for(d)

    def step(carry, xs):
        m, l, acc = carry
        kc, vc, kpos_c, kval_c = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kc.astype(jnp.float32)) * scale
        s = s + _mask_bias(q_pos, kpos_c, spec, kval_c)[:, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    rep = h // kvh
    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, sq, dv), jnp.float32)
    xs = (k.reshape(b, n_chunks, ck, kvh, d).swapaxes(0, 1),
          v.reshape(b, n_chunks, ck, kvh, dv).swapaxes(0, 1),
          k_pos.reshape(b, n_chunks, ck).swapaxes(0, 1),
          k_valid.reshape(b, n_chunks, ck).swapaxes(0, 1))
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), xs)
    o = acc / jnp.maximum(l[..., None], 1e-37)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv).astype(q.dtype)
    return o, m, l


def _chunked_backward(q, k, v, spec, q_pos, k_pos, k_valid, o, m, l, do):
    b, sq, h, d = q.shape
    kvh, vd = k.shape[2], v.shape[-1]
    sk_orig = k.shape[1]
    k, v, k_pos, k_valid, ck = _pad_chunks(q, k, v, spec, q_pos, k_pos, k_valid)
    sk = k.shape[1]
    n_chunks = sk // ck
    rep = h // kvh
    scale = spec.scale_for(d)

    qg = _group(q, kvh).astype(jnp.float32)                  # [B,Sq,g,r,D]
    og = _group(o, kvh).astype(jnp.float32)
    dog = _group(do, kvh).astype(jnp.float32)
    l_safe = jnp.maximum(l, 1e-37)                           # [B,g,r,Sq]
    D = jnp.einsum("bqgrd,bqgrd->bgrq", dog, og)             # rowsum(do*o)

    def step(dq_acc, xs):
        kc, vc, kpos_c, kval_c = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kc.astype(jnp.float32)) * scale
        s = s + _mask_bias(q_pos, kpos_c, spec, kval_c)[:, None, None]
        p = jnp.exp(s - m[..., None]) / l_safe[..., None]    # normalized
        dp = jnp.einsum("bqgrd,bkgd->bgrqk", dog, vc.astype(jnp.float32))
        ds = p * (dp - D[..., None])
        dq_acc = dq_acc + jnp.einsum("bgrqk,bkgd->bqgrd", ds,
                                     kc.astype(jnp.float32)) * scale
        dk_c = jnp.einsum("bgrqk,bqgrd->bkgd", ds, qg) * scale
        dv_c = jnp.einsum("bgrqk,bqgrd->bkgd", p, dog)
        return dq_acc, (dk_c, dv_c)

    xs = (k.reshape(b, n_chunks, ck, kvh, d).swapaxes(0, 1),
          v.reshape(b, n_chunks, ck, kvh, vd).swapaxes(0, 1),
          k_pos.reshape(b, n_chunks, ck).swapaxes(0, 1),
          k_valid.reshape(b, n_chunks, ck).swapaxes(0, 1))
    dq0 = jnp.zeros((b, sq, kvh, rep, d), jnp.float32)
    dq, (dk_chunks, dv_chunks) = jax.lax.scan(step, dq0, xs)
    dq = dq.reshape(b, sq, h, d).astype(q.dtype)
    dk = dk_chunks.swapaxes(0, 1).reshape(b, sk, kvh, d)[:, :sk_orig].astype(k.dtype)
    dv = dv_chunks.swapaxes(0, 1).reshape(b, sk, kvh, vd)[:, :sk_orig].astype(v.dtype)
    return dq, dk, dv


def _chunked_attention(q, k, v, spec, q_pos, k_pos, k_valid):
    """Online-softmax over KV chunks; optional triangular chunk skipping."""
    if not spec.causal_skip:
        return _flash_vjp_attention(q, k, v, spec, q_pos, k_pos, k_valid)
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    ck = min(spec.chunk_size, sk)
    if sk % ck != 0:  # pad KV to a chunk multiple with invalid entries
        pad = ck - sk % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2**30)
        kval = k_valid if k_valid is not None else jnp.ones((b, sk), bool)
        k_valid = jnp.pad(kval, ((0, 0), (0, pad)), constant_values=False)
        sk += pad
    n_chunks = sk // ck

    qg = _group(q, kvh).astype(jnp.float32)               # [B,Sq,KVH,rep,D]
    scale = spec.scale_for(d)

    def attend_chunk(carry, kc, vc, kpos_c, kval_c):
        m, l, acc = carry
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kc.astype(jnp.float32)) * scale
        bias = _mask_bias(q_pos, kpos_c, spec, kval_c)    # [B,Sq,Ck]
        s = s + bias[:, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new == NEG_INF): exp underflows to 0
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        # p in compute dtype for the value product: halves the bf16 residual
        # the backward saves per KV chunk (§Perf H3 iter-2); accumulation
        # stays fp32 via preferred_element_type
        acc = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((b, kvh, h // kvh, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, h // kvh, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, h // kvh, sq, d), jnp.float32)

    ks = k.reshape(b, n_chunks, ck, kvh, d).swapaxes(0, 1)
    vs = v.reshape(b, n_chunks, ck, kvh, d).swapaxes(0, 1)
    kps = k_pos.reshape(b, n_chunks, ck).swapaxes(0, 1)
    kvs = (k_valid.reshape(b, n_chunks, ck).swapaxes(0, 1)
           if k_valid is not None else None)

    if spec.causal_skip and spec.causal and sq > 1:
        # Triangular schedule: process Q chunks separately; each sees only the
        # KV chunks that can be unmasked for it.  Requires ascending,
        # chunk-aligned positions (the training/prefill layout).
        assert sq % min(spec.chunk_size, sq) == 0
        cq = min(spec.chunk_size, sq)
        nq = sq // cq
        outs = []
        for qi in range(nq):
            q_sl = slice(qi * cq, (qi + 1) * cq)
            hi = _kv_chunk_hi(qi, cq, ck)
            lo = 0
            if spec.window is not None:
                lo = max(0, (qi * cq - spec.window) // ck)
            hi = min(hi, n_chunks)
            sub = _run_chunk_scan(
                qg[:, q_sl], q_pos[:, q_sl], ks[lo:hi], vs[lo:hi], kps[lo:hi],
                None if kvs is None else kvs[lo:hi],
                spec, scale, b, kvh, h, cq, d)
            outs.append(sub)
        o = jnp.concatenate(outs, axis=1)
        return o.astype(q.dtype)

    def step(carry, xs):
        kc, vc, kpos_c, kval_c = xs
        return attend_chunk(carry, kc, vc, kpos_c, kval_c), None

    xs = (ks, vs, kps, kvs if kvs is not None else jnp.ones((n_chunks, b, ck), bool))
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), xs)
    o = acc / jnp.maximum(l[..., None], 1e-37)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)   # [B,Sq,H,D]
    return o.astype(q.dtype)


def _kv_chunk_hi(qi: int, cq: int, ck: int) -> int:
    """Last KV chunk (exclusive) visible to Q chunk qi under causality."""
    last_q_pos = (qi + 1) * cq - 1
    return last_q_pos // ck + 1


def _run_chunk_scan(qg, q_pos, ks, vs, kps, kvs, spec, scale, b, kvh, h, sq, d):
    rep = h // kvh

    def step(carry, xs):
        m, l, acc = carry
        kc, vc, kpos_c, kval_c = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kc.astype(jnp.float32)) * scale
        bias = _mask_bias(q_pos, kpos_c, spec, kval_c)
        s = s + bias[:, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, sq, d), jnp.float32)
    if kvs is None:
        kvs = jnp.ones((ks.shape[0], b, ks.shape[2]), bool)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (ks, vs, kps, kvs))
    o = acc / jnp.maximum(l[..., None], 1e-37)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


def decode_attention(
    q: jax.Array,            # [B, 1, H, D]
    k_cache: jax.Array,      # [B, S, KVH, D]
    v_cache: jax.Array,      # [B, S, KVH, D]
    k_positions: jax.Array,  # [B, S] int32 (entry positions; < 0 => invalid)
    cur_pos: jax.Array,      # [B] int32 current decode position
    window: Optional[int] = None,
) -> jax.Array:
    """Single-token attention against a (possibly ring) KV cache."""
    b, _, h, d = q.shape
    kvh = k_cache.shape[2]
    qg = _group(q, kvh).astype(jnp.float32)[:, 0]         # [B,KVH,rep,D]
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k_cache.astype(jnp.float32)) * scale
    dlt = cur_pos[:, None] - k_positions                  # [B, S]
    ok = (k_positions >= 0) & (dlt >= 0)
    if window is not None:
        ok &= dlt < window
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------- MLPs


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    h = wlc(h, "batch", "seq", "ffn")
    return h @ w_down


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
             w_out: jax.Array, b_out: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ w_in + b_in)
    h = wlc(h, "batch", "seq", "ffn")
    return h @ w_out + b_out


# ---------------------------------------------------------- qkv projections


def project_qkv(x, p, cfg):
    """x [B,S,E] -> q [B,S,H,D], k/v [B,S,KVH,D] with optional bias + padding.

    ``cfg.padded_heads`` >= real heads; the o_proj rows for padded heads are
    zero-initialized, so padded heads contribute nothing (exact equivalence —
    DESIGN.md §7).
    """
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.padded_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def make_qh_to_kv_map(num_heads: int, num_kv_heads: int,
                      padded_heads: int) -> Optional[jax.Array]:
    """Per-Q-head KV index map, or None when plain grouping is exact.

    Padding Q heads changes ``i // group`` assignments, so any padded config
    uses an explicit gather map: real head i -> i // group (original
    grouping); padded heads -> kv 0 (their o_proj rows are zero, so the
    choice is irrelevant).  KV then expands to per-Q-head layout inside
    attention (replicated-KV strategy; DESIGN.md §7).

    Every grouped (GQA) config also expands: the per-Q-head layout keeps the
    sharded dimension a clean multiple of the model axis (a [H] dim shards;
    a reshaped [KVH, rep] pair does not), which is what lets GSPMD partition
    attention without surprise all-gathers.  Pure MHA returns None.
    """
    if padded_heads == num_heads and num_kv_heads == num_heads:
        return None  # pure MHA: grouped path is already per-head
    group = max(1, num_heads // num_kv_heads)
    idx = [min(i // group, num_kv_heads - 1) if i < num_heads else 0
           for i in range(padded_heads)]
    return jnp.asarray(idx, jnp.int32)


def expand_kv(k: jax.Array, qh_to_kv: Optional[jax.Array]) -> jax.Array:
    """[B,S,KVH,D] -> [B,S,H,D] per-Q-head KV when a gather map is needed."""
    return k if qh_to_kv is None else jnp.take(k, qh_to_kv, axis=2)
