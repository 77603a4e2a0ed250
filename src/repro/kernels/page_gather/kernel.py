"""Bulk page install/evict kernels — the on-device UFFDIO_COPY analogue.

``page_gather``  copies an arbitrary set of physical pool pages into a
contiguous destination (fault resolution / prefetch batch: UMap fillers).
``page_scatter`` writes contiguous staging pages back into arbitrary pool
slots (dirty write-back: UMap evictors), updating the pool in place via
input/output aliasing — the atomic-install semantics of UFFDIO_COPY (§2.2):
a page becomes visible only as a whole.

Page indices ride in scalar-prefetch SMEM and drive the BlockSpec index maps,
so each grid step is a single page-sized DMA — no per-element gather.

A pool has any rank ``[P, *page_shape]``; the kernels view each page as
``[page_elems // 128, 128]`` (rows of 128 lanes), which is the block shape
the TPU lowers for any page whose element count is a multiple of 128.  A
pool stored as ``[P, rows, 128]`` is passed through without a relayout.
"""

from __future__ import annotations

import functools
import math

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _rows_view(pool: jax.Array) -> jax.Array:
    """``pool`` as ``[P, page_elems // 128, 128]``; raises if it cannot be."""
    elems = math.prod(pool.shape[1:])
    if elems % LANES:
        raise ValueError(
            f"page of shape {pool.shape[1:]} holds {elems} elements; the TPU "
            f"page kernels need a multiple of {LANES}")
    return pool.reshape(pool.shape[0], elems // LANES, LANES)


def _copy_kernel(ids_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather(pool: jax.Array, page_ids: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """pool: [P, *page_shape]; page_ids: [n] int32 -> [n, *page_shape]."""
    src = _rows_view(pool)
    _, rows, _ = src.shape
    n = page_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda i, ids: (ids[i], 0, 0))],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i, ids: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), pool.dtype),
        interpret=interpret,
    )(page_ids, src)
    return out.reshape((n,) + pool.shape[1:])


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def page_scatter(pool: jax.Array, page_ids: jax.Array, pages: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """Write staging ``pages`` [n, *page_shape] into ``pool`` slots ``page_ids``.

    Returns the updated pool (the input buffer is donated/aliased).
    Because of the donation, the write lands *in place*: callers must not
    have asynchronously-pending reads of the old pool value when they
    dispatch a scatter — block such gathers to completion first.
    """
    dst = _rows_view(pool)
    p, rows, _ = dst.shape
    n = page_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, ids: (i, 0, 0)),   # staging
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),          # pool alias
        ],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i, ids: (ids[i], 0, 0)),
    )
    out = pl.pallas_call(
        _copy_kernel_scatter,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, rows, LANES), pool.dtype),
        input_output_aliases={2: 0},   # pool (after 1 scalar-prefetch arg) -> out
        interpret=interpret,
    )(page_ids, pages.reshape(n, rows, LANES), dst)
    return out.reshape(pool.shape)


def _copy_kernel_scatter(ids_ref, staging_ref, pool_any_ref, out_ref):
    out_ref[...] = staging_ref[...]
