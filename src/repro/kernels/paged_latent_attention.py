"""Absorbed latent-attention decode over the paged latent pool (MLA).

One query per sequence, already taken into the latent space
(``qc = [q_nope W_uk^T, q_pe, 0]``, [B, H, W] in the pool's dtype),
attends over the sequence's cached latent rows, which lie in the
segment's stacked pool ``pool`` [count, P, ps, W] at
``pool[layer, table[b, j]]`` for its j-th page.  The result is the
softmax-weighted sum of the rows, ``o_lat`` [B, H, W] float32; the
caller decompresses it (W_uv).

``"ref"`` gathers all of the table's pages of every sequence (XLA) and
computes scores, a float32 softmax and the weighted sum over them, with
positions at or past ``lens`` masked.  ``"pallas"`` (``"interpret"`` on
the CPU) visits only each sequence's live pages, ``0 .. ceil(len/ps)-1``:
one grid step per sequence, an inner loop over blocks of pages copied
from the pool where it lies into a double buffer, the next block's copies
(the next sequence's first, at a sequence's last block) started before
the current block is used, and an online float32 softmax.  Each live row
is read from HBM once and serves both products.  Both take the products'
operands in the pool's dtype and accumulate in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_impl

#: finite mask value for scores past a sequence's length (no inf - inf)
MASK = -2.0e38

#: rows of one block of pages: a block's copies are in flight while the
#: previous block is used, so each copy has a block's compute to hide in
ROWS_PER_BLOCK = 512


def pages_per_block(page_size: int, max_pages: int) -> int:
    """Pages of one block: ``ROWS_PER_BLOCK`` rows, at most the table."""
    return max(1, min(max_pages, ROWS_PER_BLOCK // page_size))


def reads_live_pages(impl: str = "auto") -> bool:
    """Whether ``impl`` reads only the live pages (the kernel) rather
    than every table page (the gather)."""
    return resolve_impl(impl) != "ref"


def attend_rows(qc: jax.Array, rows: jax.Array, ok: jax.Array,
                scale: float) -> jax.Array:
    """qc [B, H, W] . rows [B, S, W] over the positions where ``ok``
    [B, S] holds -> [B, H, W] float32: scores and the weighted sum take
    the rows' dtype and accumulate in float32; the softmax is float32."""
    sc = jnp.einsum("bhc,bkc->bhk", qc.astype(rows.dtype), rows,
                    preferred_element_type=jnp.float32) * scale
    pr = jax.nn.softmax(jnp.where(ok[:, None, :], sc, MASK), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", pr.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32)


def visible(pos: jax.Array, lens: jax.Array, window) -> jax.Array:
    """Positions a query at ``lens - 1`` attends to: before ``lens`` and,
    with a sliding ``window``, the last ``window`` of them."""
    ok = pos < lens
    if window is not None:
        ok &= pos >= lens - window
    return ok


def paged_latent_attention(qc: jax.Array, pool: jax.Array, layer: jax.Array,
                           table: jax.Array, lens: jax.Array, scale: float,
                           window=None, impl: str = "auto") -> jax.Array:
    """qc [B, H, W]; pool [count, P, ps, W]; layer int32 []; table int32
    [B, maxp]; lens int32 [B], each at least 1 (post-write lengths) ->
    o_lat [B, H, W] float32."""
    impl = resolve_impl(impl)
    if impl == "ref":
        b, maxp = table.shape
        ps = pool.shape[2]
        rows = pool[layer, table].reshape(b, maxp * ps, pool.shape[-1])
        pos = jnp.arange(maxp * ps, dtype=jnp.int32)[None, :]
        return attend_rows(qc, rows, visible(pos, lens[:, None], window),
                           scale)
    return _paged_latent_pallas(qc, pool, layer, table, lens, scale, window,
                                interpret=impl == "interpret")


def _kernel(layer_ref, table_ref, lens_ref, q_ref, pool_ref, o_ref,
            buf, sems, slot_ref, *, scale: float, window, max_pages: int):
    b, batch = pl.program_id(0), pl.num_programs(0)
    _, ppb, ps, width = buf.shape
    bk = ppb * ps
    layer = layer_ref[0]

    def live_pages(lane):
        return pl.cdiv(lens_ref[lane], ps)

    def n_blocks(lane):
        return jnp.maximum(pl.cdiv(live_pages(lane), ppb), 1)

    def block_copies(lane, blk, slot):
        """(live, copy) of each page of block ``blk`` of ``lane``."""
        out, live = [], live_pages(lane)
        for k in range(ppb):
            j = blk * ppb + k
            page = table_ref[lane * max_pages + jnp.minimum(j, max_pages - 1)]
            out.append((j < live, pltpu.make_async_copy(
                pool_ref.at[layer, page], buf.at[slot, k], sems.at[slot])))
        return out

    def start(lane, blk, slot):
        for live, cp in block_copies(lane, blk, slot):
            pl.when(live)(cp.start)

    def wait(lane, blk, slot):
        for live, cp in block_copies(lane, blk, slot):
            pl.when(live)(cp.wait)

    @pl.when(b == 0)
    def _first():
        # rows past a lane's live pages keep what the buffer held: zeros
        # or earlier pages, finite either way, and weighted by exactly 0
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    length, nb, slot0 = lens_ref[b], n_blocks(b), slot_ref[0]
    q = q_ref[...]
    hp = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < nb)
        def _next_block():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == nb) & (b + 1 < batch))
        def _next_lane():
            start(b + 1, 0, 1 - slot)

        wait(b, i, slot)
        rows = buf[slot].reshape(bk, width)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(visible(pos, length, window), s, MASK)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(rows.dtype), rows,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((hp, 1), MASK, jnp.float32),
            jnp.zeros((hp, 1), jnp.float32),
            jnp.zeros((hp, width), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, nb, body, init)
    o_ref[...] = acc / l
    slot_ref[0] = (slot0 + nb) % 2


def _paged_latent_pallas(qc, pool, layer, table, lens, scale, window,
                         interpret):
    b, h, width = qc.shape
    _, _, ps, _ = pool.shape
    maxp = table.shape[1]
    ppb = pages_per_block(ps, maxp)
    # heads padded to the sublane tile of the pool's dtype (8 rows of 32
    # bits), so that the query and output blocks are whole tiles
    tile = 32 // pool.dtype.itemsize
    hp = -(-h // tile) * tile
    q = jnp.pad(qc.astype(pool.dtype), ((0, 0), (0, hp - h), (0, 0)))
    per_lane = pl.BlockSpec((None, hp, width), lambda i, *_: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          max_pages=maxp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[per_lane, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_lane,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, ps, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        # lanes run in order: each starts the next lane's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((b, hp, width), jnp.float32),
        interpret=interpret,
        name="paged_latent_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32), q, pool)
    return out[:, :h]
