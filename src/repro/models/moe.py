"""Mixture-of-Experts FFN: top-k router + sort-based capacity dispatch for
training, dropless grouped-matmul dispatch for serving (``moe_dropless``).

Dispatch is the sort/gather formulation (MegaBlocks/MaxText lineage) rather
than the dense [T, E, C] one-hot of GShard: assignments are sorted by expert,
positions within each expert segment become capacity slots, tokens gather into
an [E, C, d] block, experts run as batched matmuls (MXU-friendly), and results
scatter-add back with router weights.  Memory is O(T · k · cf · d); no
[T, E, C] tensor is ever materialized.

Sharding (DESIGN.md §7):
  * EP  — expert axis sharded over "model" (requires E % model == 0;
          phi3.5-moe: 16 experts on 16-way model axis).
  * TP  — expert weights replicated on E, sharded on the FFN dim
          (mixtral: 8 experts don't divide 16).
Chosen per-config via ``moe_sharding``; both use identical dispatch code —
only the parameter logical axes differ.

Aux losses (returned, summed into the training loss):
  load-balance (Switch §2.2) and router z-loss (ST-MoE).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import with_logical_constraint as wlc
from ..kernels.grouped_matmul import grouped_matmul
from .common import ParamSpec, fan_in_init, normal_init


def moe_param_specs(d_model: int, d_ff: int, num_experts: int,
                    moe_sharding: str = "ep") -> dict:
    e_ax = "expert" if moe_sharding == "ep" else None
    f_ax = None if moe_sharding == "ep" else "expert_ffn"

    def e_init(key, shape, dtype):
        return fan_in_init(key, shape, dtype, fan_in=shape[-2])

    return {
        "router": ParamSpec((d_model, num_experts), ("embed", None),
                            lambda k, s, d: normal_init(k, s, d, 0.02)),
        "w_gate": ParamSpec((num_experts, d_model, d_ff), (e_ax, "embed", f_ax), e_init),
        "w_up": ParamSpec((num_experts, d_model, d_ff), (e_ax, "embed", f_ax), e_init),
        "w_down": ParamSpec((num_experts, d_ff, d_model), (e_ax, f_ax, "embed"), e_init),
    }


def shared_expert_specs(d_model: int, d_ff: int) -> dict:
    """Always-on experts (DeepSeekMoE), merged into one SwiGLU of width
    ``n_shared x expert width``."""
    return {
        "w_gate": ParamSpec((d_model, d_ff), ("embed", "ffn"), fan_in_init),
        "w_up": ParamSpec((d_model, d_ff), ("embed", "ffn"), fan_in_init),
        "w_down": ParamSpec((d_ff, d_model), ("ffn", "embed"), fan_in_init),
    }


#: the dropless dispatch's counters, in the order ``moe_dropless`` returns
#: them: routed assignments, assignments not computed, experts hit
MOE_STATS = ("assignments", "not_computed", "experts_hit")


def moe_dropless(p: dict, x: jax.Array, top_k: int,
                 norm_topk_prob: bool = True,
                 scaling: float = 1.0, layer=None,
                 impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Dropless dispatch for prefill and decode: x [b, s, d] -> (y, stats).

    The router runs in float32 (softmax, greedy top-k; the weights are
    renormalised only with ``norm_topk_prob``, then scaled by
    ``scaling``).  Assignments are sorted by expert and each expert's rows
    go through a grouped matmul (``kernels.grouped_matmul``, ``impl``) that
    reads each routed expert's weights once and computes only the routed
    rows.  Nothing is dropped, whatever the routing's skew.

    Where ``w_gate``/``w_up``/``w_down`` are a segment's whole stack
    [count, E, k, n], ``layer``'s experts are read where they lie: the
    grouped matmul takes the stack as [count x E, k, n] (its leading axes
    merged, no copy) with this layer's groups at ``layer x E + expert``
    and every other group empty.  A layer scan that sliced the layer's
    experts out instead would copy them whole on every call, since the
    grouped matmul cannot read a slice fused.

    ``stats`` is int32 [3] in ``MOE_STATS`` order: assignments (T x k),
    assignments not computed (0: the groups cover every sorted row), and
    experts that received at least one row.
    """
    b, s, d = x.shape
    E = p["router"].shape[1]
    T, K = b * s, top_k
    x2 = x.reshape(T, d)
    logits = jnp.dot(x2.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, K)                # [T, K]
    if norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    top_w = top_w * scaling

    flat_e = top_ids.reshape(-1)                            # [T*K]
    order = jnp.argsort(flat_e, stable=True)
    tok = (order // K).astype(jnp.int32)
    groups = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    w = [p["w_gate"], p["w_up"], p["w_down"]]
    mm_groups = groups
    if w[0].ndim == 4:
        count = w[0].shape[0]
        w = [a.reshape((count * E,) + a.shape[2:]) for a in w]
        mm_groups = jax.lax.dynamic_update_slice(
            jnp.zeros(count * E, jnp.int32), groups, (layer * E,))

    def mm(a, b):
        return grouped_matmul(a, b, mm_groups, impl)

    xs = x2[tok]                                            # [T*K, d]
    h = jax.nn.silu(mm(xs, w[0])) * mm(xs, w[1])
    eo = mm(h, w[2])                                        # [T*K, d]
    w_sorted = top_w.reshape(-1)[order]
    y = jnp.zeros((T, d), jnp.float32).at[tok].add(
        eo.astype(jnp.float32) * w_sorted[:, None])
    stats = jnp.stack([jnp.int32(T * K), T * K - groups.sum(),
                       (groups > 0).sum().astype(jnp.int32)])
    return y.reshape(b, s, d).astype(x.dtype), stats


def moe_forward(p: dict, x: jax.Array, top_k: int,
                capacity_factor: float = 1.25,
                shard_local: bool = False,
                moe_sharding: str = "tp",
                norm_topk_prob: bool = True,
                scaling: float = 1.0,
                ) -> Tuple[jax.Array, dict]:
    """x: [b, s, d] -> (y [b, s, d], aux {lb_loss, z_loss, ...}).

    The training dispatch: tokens over capacity are dropped (contribute
    zero) — standard capacity-based MoE semantics; capacity_factor sizes
    the slack.  Serving uses ``moe_dropless``.

    ``shard_local=True`` (§Perf H1) wraps dispatch in shard_map so the
    sort/gather/scatter run on *local* token shards: GSPMD's auto-lowering
    of the global dispatch emits per-layer multi-GB all-reduces (the
    "involuntary full rematerialization" pattern); the local form needs only
    the usual TP psum of expert partial outputs (TP-MoE) or an expert
    all-to-all (EP).
    """
    if shard_local:
        from ..distributed.sharding import active_mesh
        mesh = active_mesh()
        if mesh is not None:
            if not norm_topk_prob or scaling != 1.0:
                raise NotImplementedError(
                    "the shard-local dispatch renormalises the top-k weights")
            return _moe_forward_shard_local(p, x, top_k, capacity_factor,
                                            moe_sharding, mesh)
    return _moe_forward_dense(p, x, top_k, capacity_factor,
                              norm_topk_prob=norm_topk_prob, scaling=scaling)


def _moe_forward_dense(p: dict, x: jax.Array, top_k: int,
                       capacity_factor: float = 1.25,
                       annotate: bool = True, norm_topk_prob: bool = True,
                       scaling: float = 1.0) -> Tuple[jax.Array, dict]:
    b, s, d = x.shape
    E = p["router"].shape[1]
    T = b * s
    x2 = x.reshape(T, d)

    logits = (x2 @ p["router"]).astype(jnp.float32)        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, top_k)            # [T, K]
    if norm_topk_prob:                                      # renorm (Mixtral)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    if scaling != 1.0:
        top_w = top_w * scaling

    K = top_k
    cap = int(max(1, round(T * K / E * capacity_factor)))

    flat_e = top_ids.reshape(-1)                            # [T*K]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                    # exclusive
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < cap
    tok_idx = (order // K).astype(jnp.int32)
    slot = sorted_e * cap + pos_in_e                        # [T*K] flat slot

    # token-index table per slot (T = out-of-band -> zero row); dropped
    # assignments get an out-of-range slot and are discarded by mode="drop"
    table = jnp.full(E * cap, T, jnp.int32)
    safe_slot = jnp.where(keep, slot, E * cap)
    table = table.at[safe_slot].set(tok_idx, mode="drop")

    x_pad = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)], axis=0)
    xg = x_pad[table].reshape(E, cap, d)                    # [E, C, d]
    if annotate:
        xg = wlc(xg, "expert", "moe_cap", "embed")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xg, p["w_up"])
    if annotate:
        h = wlc(h, "expert", "moe_cap", "expert_ffn")
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"])          # [E, C, d]
    if annotate:
        eo = wlc(eo, "expert", "moe_cap", "embed")
    eo_flat = eo.reshape(E * cap, d)

    # combine: assignment i (sorted order) reads expert-output slot[i]
    w_sorted = top_w.reshape(-1)[order].astype(eo_flat.dtype)
    contrib = eo_flat[jnp.where(keep, slot, 0)] * jnp.where(keep, w_sorted, 0.0)[:, None]
    y = jnp.zeros((T + 1, d), eo_flat.dtype).at[
        jnp.where(keep, tok_idx, T)].add(contrib)[:T]

    # aux losses
    me = probs.mean(axis=0)                                  # mean router prob
    ce = (jnp.zeros(E, jnp.float32).at[flat_e].add(1.0) / (T * K))
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - keep.sum() / (T * K)
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
           "moe_drop_frac": dropped}
    return y.reshape(b, s, d).astype(x.dtype), aux


def _moe_forward_shard_local(p: dict, x: jax.Array, top_k: int,
                             capacity_factor: float, moe_sharding: str,
                             mesh) -> Tuple[jax.Array, dict]:
    """shard_map MoE: local dispatch per data shard (§Perf H1).

    TP-MoE: expert weights replicated on E / sharded on d_ff ("model"), so
    each (data, model) shard runs the complete dispatch on its local tokens
    against its d_ff slice — the only collective is the w_down partial-sum
    psum over "model", identical to a dense TP FFN.

    EP-MoE: expert dim sharded over "model"; local dispatch is followed by an
    all_to_all that exchanges expert slots for token shards, compute runs on
    each device's own experts, and a reverse all_to_all returns outputs —
    shard-count-sized traffic instead of GSPMD's replicate+all-reduce.
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b, s, d = x.shape
    bspec = data_axes if x.shape[0] % max(
        1, int(np.prod([mesh.shape[a] for a in data_axes]))) == 0 else None

    if moe_sharding == "tp":
        pspecs = {
            "router": P(),
            "w_gate": P(None, None, "model"),
            "w_up": P(None, None, "model"),
            "w_down": P(None, "model", None),
        }

        def local(x_l, router, w_gate, w_up, w_down):
            pl = {"router": router, "w_gate": w_gate, "w_up": w_up,
                  "w_down": w_down}
            y_l, aux_l = _moe_forward_dense(pl, x_l, top_k, capacity_factor,
                                            annotate=False)
            # w_down rows are a d_ff shard -> partial outputs; finish the TP sum
            y_l = jax.lax.psum(y_l, "model")
            aux_l = {k: jax.lax.pmean(v, data_axes) for k, v in aux_l.items()}
            return y_l, aux_l

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(bspec, None, None), pspecs["router"], pspecs["w_gate"],
                      pspecs["w_up"], pspecs["w_down"]),
            out_specs=(P(bspec, None, None), P()),
            check_vma=False)
        return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    # EP: experts sharded over "model"
    def local_ep(x_l, router, w_gate, w_up, w_down):
        b_l, s_l, _ = x_l.shape
        T_full = b_l * s_l
        E = router.shape[1]
        n_model = mesh.shape["model"]   # static size (jax.lax.axis_size is newer-jax only)
        e_local = E // n_model
        x_full = x_l.reshape(T_full, d)
        # x is replicated over "model": each model peer must dispatch a
        # DISTINCT 1/n token slice, else every peer ships identical slots and
        # expert compute inflates n× (the refuted first attempt, §Perf H1b)
        split = T_full % n_model == 0 and T_full >= n_model
        if split:
            T = T_full // n_model
            mi = jax.lax.axis_index("model")
            x2 = jax.lax.dynamic_slice_in_dim(x_full, mi * T, T, axis=0)
        else:
            T = T_full
            x2 = x_full
        logits = (x2 @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_ids = jax.lax.top_k(probs, top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        K = top_k
        cap = int(max(1, round(T * K / E * capacity_factor)))

        flat_e = top_ids.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
        keep = pos_in_e < cap
        tok_idx = (order // K).astype(jnp.int32)
        slot = sorted_e * cap + pos_in_e
        table = jnp.full(E * cap, T, jnp.int32)
        table = table.at[jnp.where(keep, slot, E * cap)].set(tok_idx, mode="drop")
        x_pad = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)], axis=0)
        xg = x_pad[table].reshape(E, cap, d)             # local slots, all E

        # exchange: shard i sends its slots for expert-block j to shard j;
        # afterwards axis 0 indexes the SOURCE shard
        xg = xg.reshape(n_model, e_local, cap, d)
        xg = jax.lax.all_to_all(xg, "model", split_axis=0, concat_axis=0)
        xg = xg.swapaxes(0, 1).reshape(e_local, n_model * cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w_gate)) * \
            jnp.einsum("ecd,edf->ecf", xg, w_up)
        eo = jnp.einsum("ecf,efd->ecd", h, w_down)   # [e_local, n*cap, d]
        # reverse exchange: send each source shard its slots back
        eo = eo.reshape(e_local, n_model, cap, d).swapaxes(0, 1)
        eo = jax.lax.all_to_all(eo, "model", split_axis=0, concat_axis=0)
        eo_flat = eo.reshape(E * cap, d)

        w_sorted = top_w.reshape(-1)[order].astype(eo_flat.dtype)
        contrib = eo_flat[jnp.where(keep, slot, 0)] * \
            jnp.where(keep, w_sorted, 0.0)[:, None]
        y = jnp.zeros((T + 1, d), eo_flat.dtype).at[
            jnp.where(keep, tok_idx, T)].add(contrib)[:T]
        if split:
            # reassemble the full token range from the model-axis slices
            y = jax.lax.all_gather(y, "model", axis=0, tiled=True)

        me = probs.mean(axis=0)
        ce = (jnp.zeros(E, jnp.float32).at[flat_e].add(1.0) / (T * K))
        aux_l = {
            "moe_lb_loss": E * jnp.sum(me * ce),
            "moe_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "moe_drop_frac": 1.0 - keep.sum() / (T * K),
        }
        red_axes = data_axes + ("model",) if split else data_axes
        aux_l = {k: jax.lax.pmean(v, red_axes) for k, v in aux_l.items()}
        return y.reshape(b_l, s_l, d).astype(x_l.dtype), aux_l

    fn = shard_map(
        local_ep, mesh=mesh,
        in_specs=(P(bspec, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
