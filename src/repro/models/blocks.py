"""Decoder/encoder blocks for every segment kind, cache-aware.

Each block kind provides:
  *_specs(cfg)                     -> pytree of ParamSpec (one layer)
  apply(cfg, seg, p, x, ctx)      -> (x, new_cache_layer, aux)

``ctx`` carries the mode ("train" | "prefill" | "decode"), positions, the
per-layer cache slice, and (for cross-attention) the encoder memory.  Blocks
never see the layer stack — transformer.py scans them — except a paged
decode cache's pools, which are the segment's whole stack with this layer's
index beside them, so that the token is written where the pool lies.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Segment
from ..distributed.sharding import with_logical_constraint as wlc
from .common import ParamSpec, fan_in_init, normal_init, ones_init, zeros_init
from .layers import (
    AttnSpec,
    apply_rope,
    attention,
    decode_attention,
    expand_kv,
    gelu_mlp,
    make_qh_to_kv_map,
    rms_norm,
    swiglu,
)
from .moe import moe_forward, moe_param_specs
from .ssm import (
    mamba_decode,
    mamba_forward,
    mamba_param_specs,
    mamba_state_init,
)
from .xlstm import (
    mlstm_block_forward,
    mlstm_block_specs,
    slstm_block_forward,
    slstm_block_specs,
)


@dataclasses.dataclass
class BlockCtx:
    mode: str                                  # train | prefill | decode
    positions: jax.Array                       # [B,S] or [B,3,S] (mrope)
    cache: Optional[Dict[str, jax.Array]] = None   # this layer's cache slice
    cur_pos: Optional[jax.Array] = None        # [B] decode position
    memory: Optional[jax.Array] = None         # encoder output [B,Sm,d]
    memory_positions: Optional[jax.Array] = None


# ----------------------------------------------------------------- attention


def attn_param_specs(cfg: ModelConfig) -> dict:
    H, KV, D, E = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model

    def o_init(key, shape, dtype):
        """Zero rows for padded heads => exact function preservation."""
        w = fan_in_init(key, shape, dtype, fan_in=cfg.num_heads * D)
        if cfg.padded_heads != cfg.num_heads:
            w = w.reshape(H, D, E).at[cfg.num_heads :].set(0.0).reshape(H * D, E)
        return w

    specs = {
        "wq": ParamSpec((E, H * D), ("embed", "heads"), fan_in_init),
        "wk": ParamSpec((E, KV * D), ("embed", "kv_heads"), fan_in_init),
        "wv": ParamSpec((E, KV * D), ("embed", "kv_heads"), fan_in_init),
        "wo": ParamSpec((H * D, E), ("heads", "embed"), o_init),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((H * D,), ("heads",), zeros_init)
        specs["bk"] = ParamSpec((KV * D,), ("kv_heads",), zeros_init)
        specs["bv"] = ParamSpec((KV * D,), ("kv_heads",), zeros_init)
    return specs


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.padded_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _rope_q_positions(cfg: ModelConfig, positions: jax.Array) -> jax.Array:
    """[B,S] plain positions from possibly-mrope positions (for masks)."""
    return positions[:, 0] if positions.ndim == 3 else positions


def attn_apply(cfg: ModelConfig, seg: Segment, p: dict, x: jax.Array,
               ctx: BlockCtx, cross: bool = False):
    """Self- or cross-attention sublayer -> (out [B,S,d], new_cache)."""
    b, s, _ = x.shape
    q, k, v = _qkv(cfg, p, x)
    q_pos = _rope_q_positions(cfg, ctx.positions)

    if cross:
        # cross-attention: keys/values from encoder memory (recomputed or
        # cached at prefill; memory length static)
        mem = ctx.memory
        km = (mem @ p["wk"]).reshape(b, -1, cfg.num_kv_heads, cfg.head_dim)
        vm = (mem @ p["wv"]).reshape(b, -1, cfg.num_kv_heads, cfg.head_dim)
        spec = AttnSpec(causal=False, impl="auto", chunk_size=cfg.attn_chunk)
        qh_map = make_qh_to_kv_map(cfg.num_heads, cfg.num_kv_heads, cfg.padded_heads)
        km, vm = expand_kv(km, qh_map), expand_kv(vm, qh_map)
        o = attention(q, km, vm, spec, q_pos, ctx.memory_positions)
        o = wlc(o, "batch", "seq", "heads", "head_dim")
        return (o.reshape(b, s, -1) @ p["wo"]), None

    q = apply_rope(q, ctx.positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, ctx.positions, cfg.rope_theta, cfg.mrope_sections)
    qh_map = make_qh_to_kv_map(cfg.num_heads, cfg.num_kv_heads, cfg.padded_heads)

    window = seg.window
    causal = seg.kind != "encoder"
    new_cache = None

    if ctx.mode == "train" or (ctx.mode == "prefill" and ctx.cache is None):
        spec = AttnSpec(causal=causal, window=window, impl="auto",
                        chunk_size=cfg.attn_chunk, causal_skip=cfg.causal_skip)
        ke, ve = expand_kv(k, qh_map), expand_kv(v, qh_map)
        o = attention(q, ke, ve, spec, q_pos, q_pos)
    elif ctx.mode == "prefill":
        spec = AttnSpec(causal=causal, window=window, impl="auto",
                        chunk_size=cfg.attn_chunk, causal_skip=cfg.causal_skip)
        ke, ve = expand_kv(k, qh_map), expand_kv(v, qh_map)
        o = attention(q, ke, ve, spec, q_pos, q_pos)
        new_cache = _write_prefill_cache(ctx.cache, k, v, q_pos, window)
    elif ctx.cache is not None and "k_pool" in ctx.cache:
        # paged decode: KV lives in the segment's stacked page pool, carried
        # through the layer scan; the page table (host-managed by
        # kvcache/allocator) maps logical pages -> pool slots, and the
        # table's pages are gathered for attention.
        assert s == 1
        new_cache = _write_paged_cache(ctx.cache, k, v)
        kc, vc, kpos, cur = _gather_paged(new_cache)
        if cfg.decode_kv_expand or cfg.padded_heads != cfg.num_heads:
            kce, vce = expand_kv(kc, qh_map), expand_kv(vc, qh_map)
        else:
            kce, vce = kc, vc                  # grouped GQA (§Perf H2)
        o = decode_attention(q, kce, vce, kpos, cur, window)
        o = wlc(o, "batch", None, None, None)
        return (o.reshape(b, s, -1) @ p["wo"]), new_cache
    else:  # decode: one token against the cache
        assert s == 1
        cache = ctx.cache
        new_cache = _write_decode_cache(cache, k, v, ctx.cur_pos, window)
        kc, vc = new_cache["k"], new_cache["v"]
        # Sequence-sharded decode (flash-decoding over the model axis):
        # the cache shards on kv_seq; q and o stay replicated over "model",
        # so GSPMD lowers softmax/contraction into tiny all-reduces instead
        # of gathering the cache (DESIGN.md §7).
        q = wlc(q, "batch", None, None, None)
        kc = wlc(kc, "batch", "kv_seq", "kv_heads", "head_dim")
        vc = wlc(vc, "batch", "kv_seq", "kv_heads", "head_dim")
        if cfg.decode_kv_expand or cfg.padded_heads != cfg.num_heads:
            # baseline / padded-head path: materialize per-Q-head KV
            kce, vce = expand_kv(kc, qh_map), expand_kv(vc, qh_map)
        else:
            # §Perf H2: grouped GQA decode — q is replicated over "model" at
            # decode time, so the grouped [KVH, rep] einsum has no sharding
            # hazard and the rep× KV expansion (4× HBM traffic for llama3)
            # disappears
            kce, vce = kc, vc
        o = decode_attention(q, kce, vce, new_cache["pos"], ctx.cur_pos, window)
        o = wlc(o, "batch", None, None, None)
        return (o.reshape(b, s, -1) @ p["wo"]), new_cache

    o = wlc(o, "batch", "seq", "heads", "head_dim")
    return (o.reshape(b, s, -1) @ p["wo"]), new_cache


def _write_prefill_cache(cache, k, v, positions, window):
    """Install prefilled KV into a (possibly ring) cache."""
    S_cache = cache["k"].shape[1]
    b, s = positions.shape
    if window is not None and S_cache < s:
        # ring cache: keep the last S_cache tokens at slot = pos % S_cache
        k_tail = k[:, -S_cache:]
        v_tail = v[:, -S_cache:]
        pos_tail = positions[:, -S_cache:]
        slots = pos_tail % S_cache                      # [b, S_cache]
        bi = jnp.arange(b)[:, None]
        return {
            "k": cache["k"].at[bi, slots].set(k_tail.astype(cache["k"].dtype)),
            "v": cache["v"].at[bi, slots].set(v_tail.astype(cache["v"].dtype)),
            "pos": cache["pos"].at[bi, slots].set(pos_tail),
        }
    bi = jnp.arange(b)[:, None]
    slots = positions % S_cache if window is not None else positions
    return {
        "k": cache["k"].at[bi, slots].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[bi, slots].set(v.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[bi, slots].set(positions),
    }


def _write_paged_cache(cache, k, v):
    """Append one token through the page table, in place.

    cache: {"k_pool"/"v_pool": the segment's stacked [count, P, ps, KVH, D]
    pools, "layer": this layer's index into them, "table": [B, maxp],
    "len": [B]}.  The new token for sequence b goes to physical page
    table[b, len[b] // ps], slot len[b] % ps of pool[layer]; the pools are
    the decode scan's carry, so the scatter updates them where they lie
    (transformer._scan_segment_decode).  Page *allocation* happened
    host-side (kvcache/allocator) before this step.

    Each (page, slot, head) row of D is written on its own, which leaves
    the pool's layout to the gather: for a whole [KVH, D] token the TPU
    compiler lays the pool out with KVH and D minor, and where the gather
    wants another (KVH 3, D 64) it then copies the whole stacked pool once
    a layer.
    """
    layer = cache["layer"]
    ps = cache["k_pool"].shape[2]
    lens = cache["len"]                                   # [B]
    bi = jnp.arange(lens.shape[0])
    pages = cache["table"][bi, lens // ps][:, None]       # [B, 1]
    slots = (lens % ps)[:, None]
    heads = jnp.arange(k.shape[2])[None, :]               # [1, KVH]
    return {
        "k_pool": cache["k_pool"].at[layer, pages, slots, heads].set(
            k[:, 0].astype(cache["k_pool"].dtype)),
        "v_pool": cache["v_pool"].at[layer, pages, slots, heads].set(
            v[:, 0].astype(cache["v_pool"].dtype)),
        "layer": layer,
        "table": cache["table"],
        "len": lens + 1,
    }


def _gather_paged(cache):
    """XLA read path: gather table pages -> contiguous [B, S, KVH, D].

    The layer index is folded into the gather (``pool[layer, table]``), so
    only the table's pages are read and no per-layer pool is materialized.
    The engine decodes through this gather on every backend; the Pallas
    kernel in kernels/paged_attention lays its pool out [P, KVH, ps, D]
    and is not on this path.
    """
    b, maxp = cache["table"].shape
    ps = cache["k_pool"].shape[2]
    layer = cache["layer"]
    kc = cache["k_pool"][layer, cache["table"]]           # [B, maxp, ps, KVH, D]
    vc = cache["v_pool"][layer, cache["table"]]
    kc = kc.reshape(b, maxp * ps, *kc.shape[3:])
    vc = vc.reshape(b, maxp * ps, *vc.shape[3:])
    lens = cache["len"]                                   # post-write lengths
    pos = jnp.arange(maxp * ps, dtype=jnp.int32)[None, :]
    kpos = jnp.where(pos < lens[:, None], pos, -1)
    return kc, vc, kpos, lens - 1                          # cur_pos = len-1


def _write_decode_cache(cache, k, v, cur_pos, window):
    S_cache = cache["k"].shape[1]
    b = k.shape[0]
    slots = (cur_pos % S_cache) if window is not None else cur_pos  # [b]
    bi = jnp.arange(b)
    return {
        "k": cache["k"].at[bi, slots].set(k[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[bi, slots].set(v[:, 0].astype(cache["v"].dtype)),
        "pos": cache["pos"].at[bi, slots].set(cur_pos),
    }


def attn_cache_init(cfg: ModelConfig, seg: Segment, batch: int, seq_len: int,
                    dtype) -> Dict[str, jax.Array]:
    """Per-LAYER cache slice geometry (stacked by the caller)."""
    S = seq_len if seg.window is None else min(seq_len, seg.window)
    kvh = cfg.num_kv_heads
    return {
        "k": jnp.zeros((batch, S, kvh, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, S, kvh, cfg.head_dim), dtype),
        "pos": jnp.full((batch, S), -1, jnp.int32),
    }


# ----------------------------------------------------------------------- MLP


def mlp_param_specs(cfg: ModelConfig) -> dict:
    E, F = cfg.d_model, cfg.d_ff
    if cfg.act == "gelu":
        return {
            "w_in": ParamSpec((E, F), ("embed", "ffn"), fan_in_init),
            "b_in": ParamSpec((F,), ("ffn",), zeros_init),
            "w_out": ParamSpec((F, E), ("ffn", "embed"), fan_in_init),
            "b_out": ParamSpec((E,), ("embed",), zeros_init),
        }
    return {
        "w_gate": ParamSpec((E, F), ("embed", "ffn"), fan_in_init),
        "w_up": ParamSpec((E, F), ("embed", "ffn"), fan_in_init),
        "w_down": ParamSpec((F, E), ("ffn", "embed"), fan_in_init),
    }


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu":
        return gelu_mlp(x, p["w_in"], p["b_in"], p["w_out"], p["b_out"])
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


# --------------------------------------------------------------- block kinds


def block_param_specs(cfg: ModelConfig, seg: Segment) -> dict:
    norm = lambda: ParamSpec((cfg.d_model,), ("embed",), ones_init)
    if seg.kind in ("dense", "encoder"):
        return {"ln1": norm(), "attn": attn_param_specs(cfg),
                "ln2": norm(), "mlp": mlp_param_specs(cfg)}
    if seg.kind == "xdecoder":
        return {"ln1": norm(), "attn": attn_param_specs(cfg),
                "lnx": norm(), "xattn": attn_param_specs(cfg),
                "ln2": norm(), "mlp": mlp_param_specs(cfg)}
    if seg.kind == "moe":
        return {"ln1": norm(), "attn": attn_param_specs(cfg),
                "ln2": norm(),
                "moe": moe_param_specs(cfg.d_model, cfg.d_ff, cfg.num_experts,
                                       cfg.moe_sharding)}
    if seg.kind == "hymba":
        return {
            "ln1": norm(), "attn": attn_param_specs(cfg),
            "mamba": mamba_param_specs(cfg.d_model, cfg.d_inner, cfg.ssm_state,
                                       cfg.d_conv, cfg.dt_rank_actual),
            "beta_attn": ParamSpec((cfg.d_model,), ("embed",), ones_init),
            "beta_mamba": ParamSpec((cfg.d_model,), ("embed",), ones_init),
            "ln_attn_out": norm(), "ln_mamba_out": norm(),
            "ln2": norm(), "mlp": mlp_param_specs(cfg),
        }
    if seg.kind == "mlstm":
        return mlstm_block_specs(cfg.d_model, cfg.num_heads,
                                 cfg.mlstm_proj_factor, cfg.mlstm_qk_factor,
                                 cfg.d_conv)
    if seg.kind == "slstm":
        return slstm_block_specs(cfg.d_model, cfg.num_heads, d_conv=cfg.d_conv)
    raise ValueError(seg.kind)


def block_apply(cfg: ModelConfig, seg: Segment, p: dict, x: jax.Array,
                ctx: BlockCtx) -> Tuple[jax.Array, Any, Dict[str, jax.Array]]:
    """Returns (x_out, new_cache_layer, aux_losses)."""
    aux: Dict[str, jax.Array] = {}
    if seg.kind in ("dense", "encoder"):
        h, new_cache = attn_apply(cfg, seg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
        x = x + h
        x = x + mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        return x, new_cache, aux

    if seg.kind == "xdecoder":
        h, new_cache = attn_apply(cfg, seg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
        x = x + h
        hx, _ = attn_apply(cfg, seg, p["xattn"], rms_norm(x, p["lnx"], cfg.norm_eps),
                           ctx, cross=True)
        x = x + hx
        x = x + mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        return x, new_cache, aux

    if seg.kind == "moe":
        h, new_cache = attn_apply(cfg, seg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
        x = x + h
        y, aux = moe_forward(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps),
                             cfg.top_k, cfg.capacity_factor,
                             shard_local=cfg.moe_shard_local,
                             moe_sharding=cfg.moe_sharding)
        return x + y, new_cache, aux

    if seg.kind == "hymba":
        normed = rms_norm(x, p["ln1"], cfg.norm_eps)
        h_attn, new_attn_cache = attn_apply(cfg, seg, p["attn"], normed, ctx)
        mc = ctx.cache if ctx.cache is not None else {}
        if ctx.mode == "decode":
            h_mamba, (ssm_s, conv_s) = mamba_decode(
                p["mamba"], normed, mc.get("ssm"), mc.get("conv"),
                cfg.dt_rank_actual)
        else:
            h_mamba, (ssm_s, conv_s) = mamba_forward(
                p["mamba"], normed, mc.get("ssm"), mc.get("conv"),
                cfg.dt_rank_actual, cfg.ssm_chunk)
        fused = 0.5 * (rms_norm(h_attn, p["ln_attn_out"], cfg.norm_eps) * p["beta_attn"]
                       + rms_norm(h_mamba, p["ln_mamba_out"], cfg.norm_eps) * p["beta_mamba"])
        x = x + fused
        x = x + mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        new_cache = dict(new_attn_cache or {})
        if ctx.mode != "train":
            new_cache["ssm"] = ssm_s
            new_cache["conv"] = conv_s
        return x, (new_cache or None), aux

    if seg.kind == "mlstm":
        mc = ctx.cache if ctx.cache is not None else {}
        x, (state, conv_s) = mlstm_block_forward(
            p, x, cfg.num_heads,
            state=mc.get("state"), conv_state=mc.get("conv"),
            chunk_size=cfg.mlstm_chunk, decode=(ctx.mode == "decode"))
        new_cache = None if ctx.mode == "train" else {"state": state, "conv": conv_s}
        return x, new_cache, aux

    if seg.kind == "slstm":
        mc = ctx.cache if ctx.cache is not None else {}
        x, (state, conv_s) = slstm_block_forward(
            p, x, cfg.num_heads, state=mc.get("state"), conv_state=mc.get("conv"))
        new_cache = None if ctx.mode == "train" else {"state": state, "conv": conv_s}
        return x, new_cache, aux

    raise ValueError(seg.kind)


def block_cache_init(cfg: ModelConfig, seg: Segment, batch: int, seq_len: int,
                     dtype) -> Optional[Dict[str, jax.Array]]:
    """One layer's cache slice for this segment kind."""
    if seg.kind in ("dense", "moe", "encoder", "xdecoder"):
        return attn_cache_init(cfg, seg, batch, seq_len, dtype)
    if seg.kind == "hymba":
        c = attn_cache_init(cfg, seg, batch, seq_len, dtype)
        ssm, conv = mamba_state_init(batch, cfg.d_inner, cfg.ssm_state,
                                     cfg.d_conv, dtype)
        c["ssm"], c["conv"] = ssm, conv
        return c
    if seg.kind == "mlstm":
        d_inner = int(cfg.mlstm_proj_factor * cfg.d_model)
        dk = int(cfg.mlstm_qk_factor * d_inner) // cfg.num_heads
        dv = d_inner // cfg.num_heads
        H = cfg.num_heads
        return {
            "state": (
                jnp.zeros((batch, H, dv, dk), jnp.float32),
                jnp.zeros((batch, H, dk), jnp.float32),
                jnp.full((batch, H), -30.0, jnp.float32),
            ),
            "conv": jnp.zeros((batch, cfg.d_conv - 1, d_inner), dtype),
        }
    if seg.kind == "slstm":
        H, dh = cfg.num_heads, cfg.d_model // cfg.num_heads
        z = lambda: jnp.zeros((batch, H, dh), jnp.float32)
        return {
            "state": (z(), z(), jnp.full((batch, H, dh), -30.0, jnp.float32), z()),
            "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_model), dtype),
        }
    raise ValueError(seg.kind)
