"""Pure-jnp oracle for paged decode attention."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def paged_attention_ref(q, k_pool, v_pool, page_table, lengths):
    """Same contract as kernel.paged_attention, dense gather + softmax."""
    b, h, d = q.shape
    p_total, kvh, page_size, _ = k_pool.shape
    pages = page_table.shape[1]
    rep = h // kvh

    # gather each sequence's pages [b, pages, kvh, ps, d] into contiguous
    # [b, S, kvh, d]
    def seq(pool):
        return pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
            b, pages * page_size, kvh, d)

    k_seq, v_seq = seq(k_pool), seq(v_pool)

    qg = q.reshape(b, kvh, rep, d).astype(jnp.float32)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k_seq.astype(jnp.float32))
    s = s / math.sqrt(d)
    pos = jnp.arange(pages * page_size)
    s = jnp.where((pos[None, None, None, :] < lengths[:, None, None, None]),
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, v_seq.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)
