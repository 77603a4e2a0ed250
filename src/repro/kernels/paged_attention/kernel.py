"""Paged decode attention — attention *through the page table* (Pallas TPU).

This is the paper's core idea transplanted into the attention kernel: the KV
cache lives in a shared **page pool** ([num_pages, KVH, page_size, D], the
UMap buffer), and each sequence owns a **page table** ([B, pages_per_seq],
logical page -> physical pool page).  The kernel never sees a contiguous KV
cache; the page table rides in scalar-prefetch SMEM and drives the BlockSpec
index map, so each grid step DMAs one physical page of one KV head into VMEM
as a ``(page_size, D)`` tile (the layout's last two dims, which the TPU lowers) —
block-table indirection à la vLLM, with the UMap twist that ``page_size``
is an application-chosen knob (the paper's §3.6) swept by the benchmarks.

Grid: (batch, kv_heads, pages_per_seq); the page dimension is sequential and
carries online-softmax state in VMEM scratch.  Q rides fully in VMEM
([rep, D] per (b, kvh)).  Pages past a sequence's length map to pool page 0
and are masked by position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _paged_kernel(table_ref, length_ref,         # scalar prefetch (SMEM)
                  q_ref, k_ref, v_ref, o_ref,    # VMEM blocks
                  m_scr, l_scr, acc_scr, *,
                  page_size: int, num_pages: int, scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = length_ref[b]
    page_lo = pi * page_size

    @pl.when(page_lo < length)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)               # [rep, D]
        k = k_ref[0, 0].astype(jnp.float32)               # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)               # [page, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rep = q.shape[0]
        pos = page_lo + jax.lax.broadcasted_iota(jnp.int32, (rep, page_size), 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == num_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_table: jax.Array, lengths: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Decode attention through a paged KV pool.

    q:          [B, H, D]       one query token per sequence
    k_pool/v_pool: [P, KVH, page_size, D]  shared physical page pool
    page_table: [B, pages_per_seq] int32   logical -> physical page
    lengths:    [B] int32       tokens currently valid per sequence
    returns     [B, H, D]
    """
    b, h, d = q.shape
    p_total, kvh, page_size, _ = k_pool.shape
    pages_per_seq = page_table.shape[1]
    assert h % kvh == 0
    rep = h // kvh
    scale = 1.0 / math.sqrt(d)

    q4 = q.reshape(b, kvh, rep, d)

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, num_pages=pages_per_seq,
        scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d),
                         lambda b_, g, pi, table, lens: (b_, g, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, g, pi, table, lens: (table[b_, pi], g, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, g, pi, table, lens: (table[b_, pi], g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d),
                               lambda b_, g, pi, table, lens: (b_, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, q4, k_pool, v_pool)
    return out.reshape(b, h, d)
