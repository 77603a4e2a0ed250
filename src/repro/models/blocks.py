"""Decoder/encoder blocks for every segment kind, cache-aware.

Attention is grouped-query (per-head K and V) or, where the config sets
``kv_lora_rank``, multi-head latent attention (MLA): its cache holds one
latent row per token, and ``pool_rows`` gives each geometry's pools.

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
from ..distributed.sharding import active_mesh
from ..distributed.sharding import with_logical_constraint as wlc
from ..kernels.paged_latent_attention import (
    attend_rows,
    paged_latent_attention,
    reads_live_pages,
    visible,
)
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
    yarn_mscale,
)
from .moe import (
    MOE_STATS,
    moe_dropless,
    moe_forward,
    moe_param_specs,
    shared_expert_specs,
)
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
    layer: Optional[jax.Array] = None          # index into a stack the scan passes whole


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
        new_cache = _write_prefill_cache(ctx.cache, {"k": k, "v": v}, q_pos,
                                         window)
    elif ctx.cache is not None and "k_pool" in ctx.cache:
        # paged decode: KV lives in the segment's stacked page pool, carried
        # through the layer scan; the page table (host-managed by
        # kvcache/allocator) maps logical pages -> pool slots, and the
        # table's pages are gathered for attention.
        assert s == 1
        new_cache = _write_paged_rows(ctx.cache, {"k": k, "v": v})
        rows, kpos, cur = _gather_paged_rows(new_cache, ("k", "v"))
        kc, vc = rows["k"], rows["v"]
        if cfg.padded_heads != cfg.num_heads:
            kce, vce = expand_kv(kc, qh_map), expand_kv(vc, qh_map)
        else:
            kce, vce = kc, vc                  # grouped GQA (§Perf H2)
        o = decode_attention(q, kce, vce, kpos, cur, window)
        o = wlc(o, "batch", None, None, None)
        return (o.reshape(b, s, -1) @ p["wo"]), new_cache
    else:  # decode: one token against the cache
        assert s == 1
        cache = ctx.cache
        new_cache = _write_decode_cache(cache, {"k": k, "v": v}, ctx.cur_pos,
                                        window)
        kc, vc = new_cache["k"], new_cache["v"]
        # Sequence-sharded decode (flash-decoding over the model axis):
        # the cache shards on kv_seq; q and o stay replicated over "model",
        # so GSPMD lowers softmax/contraction into tiny all-reduces instead
        # of gathering the cache (DESIGN.md §7).
        q = wlc(q, "batch", None, None, None)
        kc = wlc(kc, "batch", "kv_seq", "kv_heads", "head_dim")
        vc = wlc(vc, "batch", "kv_seq", "kv_heads", "head_dim")
        if cfg.padded_heads != cfg.num_heads:
            # padded-head path: materialize per-Q-head KV
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


def _write_prefill_cache(cache, rows, positions, window):
    """Install prefilled rows ({name: [B, S, *row]}: K and V, or MLA's
    latent rows) into a (possibly ring) cache."""
    S_cache = cache["pos"].shape[1]
    b, s = positions.shape
    if window is not None and S_cache < s:
        # ring cache: keep the last S_cache tokens at slot = pos % S_cache
        rows = {n: x[:, -S_cache:] for n, x in rows.items()}
        positions = positions[:, -S_cache:]
    bi = jnp.arange(b)[:, None]
    slots = positions % S_cache if window is not None else positions
    out = {n: cache[n].at[bi, slots].set(x.astype(cache[n].dtype))
           for n, x in rows.items()}
    out["pos"] = cache["pos"].at[bi, slots].set(positions)
    return out


def _write_paged_rows(cache, rows):
    """Append one token's rows ({name: [B, 1, *row]} for the names of
    ``pool_rows``, whose rows share one shape) through the page table, in
    place.

    cache: {"<name>_pool": the segment's stacked [count, P, ps, *row]
    pools, "layer": this layer's index into them, "table": [B, maxp],
    "len": [B]}.  The new token for sequence b goes to physical page
    table[b, len[b] // ps], slot len[b] % ps of pool[layer]; the pools are
    the decode scan's carry, so the scatter updates them where they lie
    (transformer._scan_segment_decode).  Page *allocation* happened
    host-side (kvcache/allocator) before this step.

    A row of more than one dimension (GQA's [KVH, D]) is written per head:
    each (page, slot, head) row of D on its own, which leaves the pool's
    layout to the gather.  For a whole [KVH, D] token the TPU compiler lays
    the pool out with KVH and D minor, and where the gather wants another
    (KVH 3, D 64) it then copies the whole stacked pool once a layer.
    """
    layer, lens = cache["layer"], cache["len"]            # [B]
    name, first = next(iter(rows.items()))
    ps = cache[f"{name}_pool"].shape[2]
    per_head = first.ndim > 3
    # page and slot per sequence: [B, 1] beside the heads' [1, KVH], or [B]
    col = (slice(None), None) if per_head else (slice(None),)
    bi = jnp.arange(lens.shape[0])
    at = (layer, cache["table"][bi, lens // ps][col], (lens % ps)[col])
    if per_head:
        at += (jnp.arange(first.shape[2])[None, :],)      # [1, KVH]
    pools = {f"{n}_pool": cache[f"{n}_pool"].at[at].set(
                 x[:, 0].astype(cache[f"{n}_pool"].dtype))
             for n, x in rows.items()}
    return {**pools, "layer": layer, "table": cache["table"],
            "len": lens + 1}


def _gather_paged_rows(cache, names):
    """XLA read path: this layer's table pages of each named pool ->
    ({name: [B, maxp * ps, *row]}, kpos [B, maxp * ps], cur_pos [B]).

    The layer index is folded into the gather (``pool[layer, table]``), so
    only the table's pages are read and no per-layer pool is materialized.
    ``kpos`` is a position's index, or -1 at and past the sequence's
    post-write length; cur_pos is that length - 1.
    """
    b, maxp = cache["table"].shape
    ps = cache[f"{names[0]}_pool"].shape[2]
    pages = {n: cache[f"{n}_pool"][cache["layer"], cache["table"]]
             for n in names}                              # [B, maxp, ps, *row]
    rows = {n: x.reshape(b, maxp * ps, *x.shape[3:]) for n, x in pages.items()}
    lens = cache["len"]                                   # post-write lengths
    pos = jnp.arange(maxp * ps, dtype=jnp.int32)[None, :]
    return rows, jnp.where(pos < lens[:, None], pos, -1), lens - 1


def _write_decode_cache(cache, rows, cur_pos, window):
    """Write one token's rows ({name: [B, 1, *row]}) at ``cur_pos`` (its
    ring slot in a windowed cache)."""
    S_cache = cache["pos"].shape[1]
    slots = (cur_pos % S_cache) if window is not None else cur_pos  # [b]
    bi = jnp.arange(cur_pos.shape[0])
    out = {n: cache[n].at[bi, slots].set(x[:, 0].astype(cache[n].dtype))
           for n, x in rows.items()}
    out["pos"] = cache["pos"].at[bi, slots].set(cur_pos)
    return out


def pool_rows(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """What an attention layer caches per token: {name: row shape}.  The
    contiguous cache holds ``name`` [B, S, *row] and the engine's paged
    cache ``<name>_pool`` [count, pages, page_size, *row].  GQA caches
    per-head keys and values; MLA one row of the normalised latent and the
    roped shared key (kv_lora_rank + qk_rope_head_dim, zero-padded to
    ``mla_row_width``)."""
    if cfg.mla:
        return {"ckv": (mla_row_width(cfg),)}
    row = (cfg.num_kv_heads, cfg.head_dim)
    return {"k": row, "v": row}


def paged_reads_live_pages(cfg: ModelConfig) -> bool:
    """Whether paged decode reads only each lane's live pages: MLA's
    latent reader off the CPU (``kernels.paged_latent_attention``); GQA's
    ``_gather_paged_rows`` reads every table page."""
    return cfg.mla and reads_live_pages()


#: MLA's cached row is padded with zeros to a multiple of the TPU's lane
#: width: for a 576-wide row (DeepSeek-V2) the device's default layout of
#: the stacked pool puts pages minor, and the decode step then relayouts
#: the whole pool into its layer loop and out of it (AOT for a v5e); at 640
#: the pool keeps rows minor, which the tiled layout pads 576 to anyway.
MLA_ROW_MULTIPLE = 128


def mla_row_width(cfg: ModelConfig) -> int:
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim)
             // MLA_ROW_MULTIPLE) * MLA_ROW_MULTIPLE


def attn_cache_init(cfg: ModelConfig, seg: Segment, batch: int, seq_len: int,
                    dtype, ring: bool = True) -> Dict[str, jax.Array]:
    """Per-LAYER cache slice geometry (stacked by the caller).  A windowed
    segment gets a ring of ``window`` slots unless ``ring`` is False (the
    engine's prefill keeps every prompt position for its pool pages)."""
    S = seq_len if seg.window is None or not ring else min(seq_len, seg.window)
    out = {n: jnp.zeros((batch, S) + row, dtype)
           for n, row in pool_rows(cfg).items()}
    out["pos"] = jnp.full((batch, S), -1, jnp.int32)
    return out


# ----------------------------------------------------- latent attention (MLA)


def mla_param_specs(cfg: ModelConfig) -> dict:
    """DeepSeek-V2 MLA without q LoRA; every matrix [in, out]."""
    H, E, r = cfg.num_heads, cfg.d_model, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((E, H * (dn + dr)), ("embed", "heads"), fan_in_init),
        "wkv_a": ParamSpec((E, r + dr), ("embed", None), fan_in_init),
        "kv_norm": ParamSpec((r,), (None,), ones_init),
        "wkv_b": ParamSpec((r, H * (dn + dv)), (None, "heads"), fan_in_init),
        "wo": ParamSpec((H * dv, E), ("heads", "embed"), fan_in_init),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(nope + rope dims), times yarn's mscale(mscale_all_dim)^2."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn is not None and cfg.rope_yarn[5]:
        scale *= yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) ** 2
    return scale


def mla_apply(cfg: ModelConfig, seg: Segment, p: dict, x: jax.Array,
              ctx: BlockCtx):
    """MLA sublayer -> (out [B,S,d], new_cache).

    Train and prefill decompress each head's keys and values from the
    latent (k = [c W_uk, k_pe], v = c W_uv) and attend causally.  Decode is
    absorbed: the query is taken into the latent space (q_nope W_uk^T), so
    scores and the weighted sum run over the cached latent rows directly,
    and only the head outputs are decompressed (W_uv); the paged pool is
    read by ``kernels.paged_latent_attention``.  The cache holds,
    per token, the normalised latent and the roped shared key (one row of
    ``kv_lora_rank + qk_rope_head_dim``, zero-padded to ``mla_row_width``)."""
    b, s, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (x @ p["wq"]).reshape(b, s, H, dn + dr)
    kv_a = x @ p["wkv_a"]
    c = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    q_pe = apply_rope(q[..., dn:], ctx.positions, cfg.rope_theta,
                      yarn=cfg.rope_yarn)
    k_pe = apply_rope(kv_a[..., None, r:], ctx.positions, cfg.rope_theta,
                      yarn=cfg.rope_yarn)                  # [B,S,1,dr]
    pad = mla_row_width(cfg) - r - dr
    row = jnp.concatenate([c, k_pe[:, :, 0],
                           jnp.zeros((b, s, pad), c.dtype)], axis=-1)
    scale = mla_softmax_scale(cfg)
    q_pos = _rope_q_positions(cfg, ctx.positions)

    if ctx.mode == "decode":
        assert s == 1
        w = p["wkv_b"].reshape(r, H, dn + dv)
        qc = _mla_latent_query(w, q[:, 0, :, :dn], q_pe[:, 0], row.shape[-1])
        if "ckv_pool" in ctx.cache:
            # the latent pool's one reader: only each lane's live pages on
            # the TPU, every table page on the CPU (DESIGN.md §19.5)
            new_cache = _write_paged_rows(ctx.cache, {"ckv": row})
            o_lat = paged_latent_attention(
                qc, new_cache["ckv_pool"], new_cache["layer"],
                new_cache["table"], new_cache["len"], scale, seg.window)
        else:
            new_cache = _write_decode_cache(ctx.cache, {"ckv": row},
                                            ctx.cur_pos, seg.window)
            kpos, cur = new_cache["pos"], ctx.cur_pos[:, None]
            ok = (kpos >= 0) & visible(kpos, cur + 1, seg.window)
            o_lat = attend_rows(qc, new_cache["ckv"], ok, scale)
        o = jnp.einsum("bhr,rhv->bhv", o_lat[..., :r].astype(w.dtype),
                       w[..., dn:], preferred_element_type=jnp.float32)
        return o.astype(q.dtype).reshape(b, s, H * dv) @ p["wo"], new_cache

    kv = (c @ p["wkv_b"]).reshape(b, s, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, H, dr))], axis=-1)
    qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    spec = AttnSpec(causal=True, window=seg.window, impl="auto",
                    chunk_size=cfg.attn_chunk, scale=scale)
    o = attention(qf, k, kv[..., dn:], spec, q_pos, q_pos)
    new_cache = None
    if ctx.mode == "prefill" and ctx.cache is not None:
        new_cache = _write_prefill_cache(ctx.cache, {"ckv": row}, q_pos,
                                         seg.window)
    o = wlc(o, "batch", "seq", "heads", "head_dim")
    return o.reshape(b, s, H * dv) @ p["wo"], new_cache


def _mla_latent_query(w, q_nope, q_pe, width):
    """The absorbed query [q_nope W_uk^T, q_pe, 0] [B, H, width], float32,
    against cached latent rows (c, k_pe, zero padding); ``w`` is W_kv_b
    as [r, H, nope + v]."""
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w[..., :q_nope.shape[-1]],
                       preferred_element_type=jnp.float32)
    pad = width - q_lat.shape[-1] - q_pe.shape[-1]
    return jnp.concatenate([q_lat, q_pe.astype(jnp.float32),
                            jnp.zeros(q_lat.shape[:2] + (pad,), jnp.float32)],
                           -1)


def self_attn_apply(cfg: ModelConfig, seg: Segment, p: dict, x: jax.Array,
                    ctx: BlockCtx):
    return (mla_apply if cfg.mla else attn_apply)(cfg, seg, p, x, ctx)


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
    attn = mla_param_specs if cfg.mla else attn_param_specs
    if seg.kind in ("dense", "encoder"):
        return {"ln1": norm(), "attn": attn(cfg),
                "ln2": norm(), "mlp": mlp_param_specs(cfg)}
    if seg.kind == "xdecoder":
        return {"ln1": norm(), "attn": attn_param_specs(cfg),
                "lnx": norm(), "xattn": attn_param_specs(cfg),
                "ln2": norm(), "mlp": mlp_param_specs(cfg)}
    if seg.kind == "moe":
        out = {"ln1": norm(), "attn": attn(cfg), "ln2": norm(),
               "moe": moe_param_specs(cfg.d_model, cfg.expert_d_ff,
                                      cfg.num_experts, cfg.moe_sharding)}
        if cfg.num_shared_experts:
            out["shared"] = shared_expert_specs(
                cfg.d_model, cfg.num_shared_experts * cfg.expert_d_ff)
        return out
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


def moe_is_dropless(cfg: ModelConfig, mode: str) -> bool:
    """Whether a MoE block dispatches dropless (serving) rather than by
    capacity (training, and the sharded dry-run path)."""
    return mode != "train" and not (cfg.moe_shard_local and active_mesh())


def block_apply(cfg: ModelConfig, seg: Segment, p: dict, x: jax.Array,
                ctx: BlockCtx) -> Tuple[jax.Array, Any, Dict[str, jax.Array]]:
    """Returns (x_out, new_cache_layer, aux_losses)."""
    aux: Dict[str, jax.Array] = {}
    if seg.kind in ("dense", "encoder"):
        h, new_cache = self_attn_apply(cfg, seg, p["attn"],
                                       rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
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
        h, new_cache = self_attn_apply(cfg, seg, p["attn"],
                                       rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
        x = x + h
        hn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if not moe_is_dropless(cfg, ctx.mode):
            y, aux = moe_forward(p["moe"], hn, cfg.top_k, cfg.capacity_factor,
                                 shard_local=cfg.moe_shard_local,
                                 moe_sharding=cfg.moe_sharding,
                                 norm_topk_prob=cfg.norm_topk_prob,
                                 scaling=cfg.routed_scaling_factor)
        else:
            y, stats = moe_dropless(p["moe"], hn, cfg.top_k,
                                    cfg.norm_topk_prob,
                                    cfg.routed_scaling_factor, ctx.layer)
            if new_cache is not None and "moe_stats" in ctx.cache:
                new_cache["moe_stats"] = ctx.cache["moe_stats"] + stats
        if cfg.num_shared_experts:
            y = y + mlp_apply(cfg, p["shared"], hn)
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
                     dtype, ring: bool = True) -> Optional[Dict[str, jax.Array]]:
    """One layer's cache slice for this segment kind (``ring``: see
    ``attn_cache_init``)."""
    if seg.kind in ("dense", "moe", "encoder", "xdecoder"):
        c = attn_cache_init(cfg, seg, batch, seq_len, dtype, ring)
        if seg.kind == "moe":     # on-device counters (moe.MOE_STATS)
            c["moe_stats"] = jnp.zeros((len(MOE_STATS),), jnp.int32)
        return c
    if seg.kind == "hymba":
        c = attn_cache_init(cfg, seg, batch, seq_len, dtype, ring)
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
