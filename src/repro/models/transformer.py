"""Model assembly: decoder-only LM and encoder-decoder, scan-over-layers.

Entry points (all pure functions of (cfg, params, ...)):

  param_specs / init_params       parameter pytree (segments stacked for scan)
  forward_train                   [B,S] tokens (or embeds) -> hidden + aux
  lm_logits                       hidden -> masked logits (padded vocab)
  init_cache                      cache pytree for (batch, seq_len)
  prefill                         writes cache, returns last-position hidden
  decode_step                     one token per sequence through the cache

Layer stacks lower as one ``jax.lax.scan`` per homogeneous segment
(ModelConfig.layer_plan), keeping HLO size O(#segment-kinds), which is what
makes 512-device compiles of 32–48-layer models tractable.  ``cfg.remat``
wraps each scanned block in ``jax.checkpoint`` for training.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Segment
from ..distributed.sharding import with_logical_constraint as wlc
from .blocks import (
    BlockCtx,
    block_apply,
    block_cache_init,
    block_param_specs,
    moe_is_dropless,
)
from .common import (
    ParamSpec,
    init_param_tree,
    logical_axes_tree,
    normal_init,
    ones_init,
    stack_specs,
)

NEG_INF = -1.0e9


def cast_params(cfg: ModelConfig, params: dict) -> dict:
    """Cast float params to the compute dtype (mixed-precision forward).

    Master params stay in ``param_dtype`` (fp32); the cast is traced into the
    jitted step so XLA fuses it with first use, and its transpose upcasts
    gradients back to fp32 for the optimizer.
    """
    compute = jnp.dtype(cfg.compute_dtype)

    def cast(a):
        if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != compute:
            return a.astype(compute)
        return a

    return jax.tree.map(cast, params)


# ------------------------------------------------------------------- params


def param_specs(cfg: ModelConfig) -> dict:
    specs: Dict[str, Any] = {}
    if cfg.input_mode == "tokens" or not cfg.is_encdec:
        specs["embed"] = ParamSpec((cfg.padded_vocab, cfg.d_model),
                                   ("vocab", "embed"),
                                   lambda k, s, d: normal_init(k, s, d, 0.02))
    if cfg.is_encdec:
        # decoder token embedding (encoder consumes stub embeds directly)
        specs["embed"] = ParamSpec((cfg.padded_vocab, cfg.d_model),
                                   ("vocab", "embed"),
                                   lambda k, s, d: normal_init(k, s, d, 0.02))
        specs["encoder"] = [
            stack_specs(block_param_specs(cfg, seg), seg.count)
            for seg in cfg.encoder_plan()
        ]
        specs["enc_norm"] = ParamSpec((cfg.d_model,), ("embed",), ones_init)
    specs["segments"] = [
        stack_specs(block_param_specs(cfg, seg), seg.count)
        for seg in cfg.decoder_plan()
    ]
    specs["final_norm"] = ParamSpec((cfg.d_model,), ("embed",), ones_init)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.padded_vocab),
                                     ("embed", "vocab"),
                                     lambda k, s, d: normal_init(k, s, d, 0.02))
    if cfg.num_meta_tokens:
        specs["meta_tokens"] = ParamSpec((cfg.num_meta_tokens, cfg.d_model),
                                         (None, "embed"),
                                         lambda k, s, d: normal_init(k, s, d, 0.02))
    return specs


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    return init_param_tree(param_specs(cfg), rng, dtype)


def param_logical_axes(cfg: ModelConfig) -> dict:
    return logical_axes_tree(param_specs(cfg))


# -------------------------------------------------------------------- embed


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(jnp.dtype(cfg.compute_dtype))


def lm_logits(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    """hidden [.., d] -> logits [.., padded_vocab], padded region masked."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = hidden @ w.astype(hidden.dtype)
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(mask, NEG_INF, logits)
    axes = ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",)
    return wlc(logits, *axes)


# ---------------------------------------------------------------- scan plumb


def _routed_experts(cfg: ModelConfig, seg: Segment, seg_params, mode: str):
    """(params to scan, stacked routed experts or None).

    A dropless MoE segment's ``w_gate``/``w_up``/``w_down`` [count, E, k, n]
    stay out of the scanned ``xs``: each layer hands the whole stack to
    ``moe.moe_dropless`` with its layer index, which reads the layer's
    experts where they lie.  Scanned, each layer's experts would be a
    slice, which the grouped matmul cannot take fused, so the compiler
    would copy them whole on every call."""
    if seg.kind != "moe" or not moe_is_dropless(cfg, mode):
        return seg_params, None
    moe = dict(seg_params["moe"])
    experts = {k: moe.pop(k) for k in ("w_gate", "w_up", "w_down")}
    return {**seg_params, "moe": moe}, experts


def _with_experts(layer_p, experts):
    if experts is None:
        return layer_p
    return {**layer_p, "moe": {**layer_p["moe"], **experts}}


def _scan_segment(cfg: ModelConfig, seg: Segment, seg_params, x, ctx: BlockCtx,
                  cache_seg, collect_aux: bool):
    """Scan one homogeneous segment.  cache_seg: stacked [count, ...] or None."""
    aux0 = {"moe_lb_loss": jnp.zeros((), jnp.float32),
            "moe_z_loss": jnp.zeros((), jnp.float32),
            "moe_drop_frac": jnp.zeros((), jnp.float32)} if collect_aux else None
    seg_params, experts = _routed_experts(cfg, seg, seg_params, ctx.mode)
    layers = None if experts is None else jnp.arange(seg.count, dtype=jnp.int32)

    def body(carry, xs):
        x, aux_acc = carry
        layer_p, cache_l, layer = xs
        if _is_dummy(cache_l):
            cache_l = None
        lctx = BlockCtx(mode=ctx.mode, positions=ctx.positions, cache=cache_l,
                        cur_pos=ctx.cur_pos, memory=ctx.memory,
                        memory_positions=ctx.memory_positions, layer=layer)
        layer_p = _with_experts(layer_p, experts)
        x, new_cache, aux = block_apply(cfg, seg, layer_p, x, lctx)
        if aux_acc is not None and aux:
            aux_acc = {k: aux_acc[k] + aux[k].astype(jnp.float32) for k in aux_acc}
        return (x, aux_acc), new_cache

    if cfg.remat == "full" and ctx.mode == "train":
        body = jax.checkpoint(body)

    (x, aux_acc), new_caches = jax.lax.scan(
        body, (x, aux0),
        (seg_params, cache_seg if cache_seg is not None
         else _none_like_scan(seg.count), layers))
    return x, aux_acc, new_caches


def _none_like_scan(count: int):
    # scan needs a pytree with a leading axis; use a dummy zeros array that
    # blocks ignore (cache=None is represented by this sentinel)
    return jnp.zeros((count, 0), jnp.float32)


def _is_dummy(cache) -> bool:
    return isinstance(cache, jax.Array) and cache.size == 0


# ------------------------------------------------------------------ forward


def _decoder_stack(cfg: ModelConfig, params: dict, x, ctx: BlockCtx,
                   caches: Optional[list], collect_aux: bool):
    plan = cfg.decoder_plan()
    new_caches = []
    aux_total: Dict[str, jax.Array] = {}
    for i, seg in enumerate(plan):
        cache_seg = caches[i] if caches is not None else None
        seg_ctx = ctx
        x, aux_acc, nc = _scan_segment(cfg, seg, params["segments"][i], x,
                                       seg_ctx, cache_seg, collect_aux)
        new_caches.append(nc)
        if aux_acc:
            for k, v in aux_acc.items():
                aux_total[k] = aux_total.get(k, 0.0) + v
    if aux_total:
        n_layers = float(cfg.num_layers)
        aux_total = {k: v / n_layers for k, v in aux_total.items()}
    return x, new_caches, aux_total


def _input_hidden(cfg: ModelConfig, params: dict, batch: dict) -> Tuple[jax.Array, jax.Array]:
    """Returns (x [B,S',d], positions [B,S'] or [B,3,S']) with meta prefix."""
    if cfg.input_mode == "embeds":
        x = batch["embeds"].astype(jnp.dtype(cfg.compute_dtype))
        positions = batch.get("positions")
        if positions is None:
            b, s = x.shape[:2]
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    else:
        tokens = batch["tokens"]
        x = embed_tokens(cfg, params, tokens)
        b, s = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.num_meta_tokens:
        b = x.shape[0]
        meta = jnp.broadcast_to(
            params["meta_tokens"].astype(x.dtype),
            (b, cfg.num_meta_tokens, cfg.d_model))
        x = jnp.concatenate([meta, x], axis=1)
        m = cfg.num_meta_tokens
        if positions.ndim == 3:
            mpos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, 3, m))
            positions = jnp.concatenate([mpos, positions + m], axis=2)
        else:
            mpos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))
            positions = jnp.concatenate([mpos, positions + m], axis=1)
    return x, positions


def _encode(cfg: ModelConfig, params: dict, batch: dict):
    """Encoder stack over stub frame embeddings -> memory [B,Sm,d]."""
    x = batch["src_embeds"].astype(jnp.dtype(cfg.compute_dtype))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    ctx = BlockCtx(mode="train", positions=positions)
    from .layers import rms_norm
    for i, seg in enumerate(cfg.encoder_plan()):
        x, _, _ = _scan_segment(cfg, seg, params["encoder"][i], x, ctx, None, False)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps), positions


def forward_train(cfg: ModelConfig, params: dict, batch: dict
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward -> (hidden [B,S,d] after final norm, aux).

    The meta-token prefix (hymba) is stripped from the returned hidden so
    loss code sees exactly the input sequence length.
    """
    from .layers import rms_norm

    params = cast_params(cfg, params)
    memory = memory_pos = None
    if cfg.is_encdec:
        memory, memory_pos = _encode(cfg, params, batch)
    x, positions = _input_hidden(cfg, params, batch)
    x = wlc(x, "batch", "seq", "embed")
    ctx = BlockCtx(mode="train", positions=positions, memory=memory,
                   memory_positions=memory_pos)
    x, _, aux = _decoder_stack(cfg, params, x, ctx, None,
                               collect_aux=cfg.num_experts > 0)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.num_meta_tokens:
        x = x[:, cfg.num_meta_tokens:]
    return x, aux


# -------------------------------------------------------------------- cache


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None,
               memory_len: Optional[int] = None, ring: bool = True) -> list:
    """Stacked per-segment caches sized for ``seq_len`` total positions;
    ``ring=False`` gives windowed segments every position too (a prefill
    whose rows go to pool pages)."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    caches = []
    for seg in cfg.decoder_plan():
        layer = block_cache_init(cfg, seg, batch, seq_len, dtype, ring)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (seg.count,) + a.shape).copy(), layer)
        if cfg.is_encdec and seg.kind == "xdecoder":
            ml = memory_len or seq_len
            stacked["xk"] = jnp.zeros(
                (seg.count, batch, ml, cfg.num_kv_heads, cfg.head_dim), dtype)
            stacked["xv"] = jnp.zeros_like(stacked["xk"])
        caches.append(stacked)
    return caches


def prefill(cfg: ModelConfig, params: dict, batch: dict, cache: list
            ) -> Tuple[jax.Array, list]:
    """Run the prompt, writing caches.  Returns (last hidden [B,d], cache)."""
    from .layers import rms_norm

    params = cast_params(cfg, params)
    memory = memory_pos = None
    if cfg.is_encdec:
        memory, memory_pos = _encode(cfg, params, batch)
        cache = _fill_cross_kv(cfg, params, cache, memory)
    x, positions = _input_hidden(cfg, params, batch)
    x = wlc(x, "batch", "seq", "embed")
    ctx = BlockCtx(mode="prefill", positions=positions, memory=memory,
                   memory_positions=memory_pos)
    new_caches = []
    for i, seg in enumerate(cfg.decoder_plan()):
        cache_seg = {k: v for k, v in cache[i].items() if k not in ("xk", "xv")} \
            if isinstance(cache[i], dict) else cache[i]
        x, _, nc = _scan_segment(cfg, seg, params["segments"][i], x, ctx,
                                 cache_seg, False)
        if isinstance(cache[i], dict) and "xk" in cache[i]:
            nc = dict(nc)
            nc["xk"], nc["xv"] = cache[i]["xk"], cache[i]["xv"]
        new_caches.append(nc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, -1], new_caches


def _fill_cross_kv(cfg: ModelConfig, params: dict, cache: list, memory):
    """Precompute per-layer cross-attention KV from encoder memory."""
    b, sm, _ = memory.shape
    out = []
    for i, seg in enumerate(cfg.decoder_plan()):
        c = dict(cache[i])
        if seg.kind == "xdecoder":
            def per_layer(p):
                k = (memory @ p["xattn"]["wk"]).reshape(b, sm, cfg.num_kv_heads, cfg.head_dim)
                v = (memory @ p["xattn"]["wv"]).reshape(b, sm, cfg.num_kv_heads, cfg.head_dim)
                return k.astype(c["xk"].dtype), v.astype(c["xv"].dtype)
            ks, vs = jax.vmap(per_layer)(params["segments"][i])
            c["xk"], c["xv"] = ks, vs
        out.append(c)
    return out


def decode_step(cfg: ModelConfig, params: dict, cache: list,
                tokens: jax.Array, cur_pos: jax.Array
                ) -> Tuple[jax.Array, list]:
    """One decode step.  tokens: [B] int32; cur_pos: [B] absolute position.

    Returns (logits [B, padded_vocab], new cache).
    """
    from .layers import rms_norm

    params = cast_params(cfg, params)
    b = tokens.shape[0]
    x = embed_tokens(cfg, params, tokens[:, None])
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(cur_pos[:, None, None], (b, 3, 1))
    else:
        positions = cur_pos[:, None]
    x = wlc(x, "batch", "seq", "embed")
    new_caches = []
    for i, seg in enumerate(cfg.decoder_plan()):
        cache_seg = cache[i]
        memory = None
        if seg.kind == "xdecoder":
            memory = "cached"  # sentinel: cross KV read from cache
        ctx = BlockCtx(mode="decode", positions=positions, cur_pos=cur_pos)
        x, _, nc = _scan_segment_decode(cfg, seg, params["segments"][i], x,
                                        ctx, cache_seg)
        new_caches.append(nc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return lm_logits(cfg, params, x[:, 0]), new_caches


def _scan_segment_decode(cfg: ModelConfig, seg: Segment, seg_params, x,
                         ctx: BlockCtx, cache_seg):
    """Decode-mode scan; handles cached cross-attention KV for enc-dec.

    A paged cache's stacked pools (``<name>_pool`` [count, P, ps, *row]:
    ``k_pool``/``v_pool`` for GQA, ``ckv_pool`` for MLA; blocks.pool_rows)
    ride in the scan carry with the layer index: each layer writes its
    token into them in place and reads its pages from them directly
    (blocks._write_paged_rows; blocks._gather_paged_rows for GQA,
    kernels.paged_latent_attention for MLA), so no per-layer pool is
    sliced out and no fresh stacked pool is written back, and a caller
    that donates the caches gets the pools updated where they lie.  A MoE
    segment's stacked routed experts are read in place the same way
    (``_routed_experts``).  Everything else (dense and
    ring caches, page tables, lengths, recurrent state, MoE counters)
    scans as ``xs``/``ys``.
    """
    seg_params, experts = _routed_experts(cfg, seg, seg_params, ctx.mode)
    pools = {}
    if isinstance(cache_seg, dict):
        pools = {k: v for k, v in cache_seg.items() if k.endswith("_pool")}
        cache_seg = {k: v for k, v in cache_seg.items() if k not in pools}
    pool_keys = tuple(pools)

    def body(carry, xs):
        x, layer, pools = carry
        layer_p, cache_l = xs
        if pools:
            cache_l = {**cache_l, **pools, "layer": layer}
        lctx = BlockCtx(mode="decode", positions=ctx.positions,
                        cache=cache_l, cur_pos=ctx.cur_pos, layer=layer)
        layer_p = _with_experts(layer_p, experts)
        if seg.kind == "xdecoder":
            x, new_cache, _ = _xdecoder_decode(cfg, seg, layer_p, x, lctx)
        else:
            x, new_cache, _ = block_apply(cfg, seg, layer_p, x, lctx)
            if isinstance(cache_l, dict) and "xk" in cache_l:
                new_cache["xk"], new_cache["xv"] = cache_l["xk"], cache_l["xv"]
        if pools:
            pools = {k: new_cache[k] for k in pool_keys}
            new_cache = {k: v for k, v in new_cache.items()
                         if k not in pool_keys + ("layer",)}
        return (x, layer + 1, pools), new_cache

    (x, _, pools), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.int32), pools), (seg_params, cache_seg))
    if pools:
        new_caches = {**new_caches, **pools}
    return x, None, new_caches


def _xdecoder_decode(cfg: ModelConfig, seg: Segment, p, x, ctx: BlockCtx):
    """Decoder-with-cross-attention decode step using cached cross KV."""
    from .blocks import attn_apply
    from .layers import decode_attention, expand_kv, make_qh_to_kv_map, rms_norm

    cache = ctx.cache
    self_cache = {k: cache[k] for k in ("k", "v", "pos")}
    sctx = BlockCtx(mode="decode", positions=ctx.positions,
                    cache=self_cache, cur_pos=ctx.cur_pos)
    h, new_self = attn_apply(cfg, seg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), sctx)
    x = x + h

    # cross attention from cached memory KV (full validity)
    b, s, _ = x.shape
    hq = rms_norm(x, p["lnx"], cfg.norm_eps)
    q = (hq @ p["xattn"]["wq"]).reshape(b, s, cfg.padded_heads, cfg.head_dim)
    qh_map = make_qh_to_kv_map(cfg.num_heads, cfg.num_kv_heads, cfg.padded_heads)
    xk, xv = expand_kv(cache["xk"], qh_map), expand_kv(cache["xv"], qh_map)
    sm = xk.shape[1]
    mem_pos = jnp.broadcast_to(jnp.arange(sm, dtype=jnp.int32), (b, sm))
    big = jnp.full((b,), 2**30, jnp.int32)   # no causal limit for cross-attn
    o = decode_attention(q, xk, xv, mem_pos, big, None)
    x = x + (o.reshape(b, s, -1) @ p["xattn"]["wo"])

    from .blocks import mlp_apply
    x = x + mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
    new_cache = dict(new_self)
    new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
    return x, new_cache, {}
