"""Grouped matmul of the routed experts (``moe.moe_dropless``).

``lhs`` [m, k] holds rows sorted by group; ``group_sizes`` [G] counts each
group's rows, and group g's rows are multiplied by ``rhs[g]`` [k, n].  The
groups may cover fewer than m rows and most of them may be empty: the
routed experts of one layer are a block of groups in a stack of every
layer's experts, read where they lie.

``"ref"`` is ``jax.lax.ragged_dot``.  ``"pallas"`` (``"interpret"`` on the
CPU) is the Pallas grouped matmul that ships with JAX (megablox ``gmm``),
whose grid visits only the tiles of non-empty groups, so an empty group's
weights are never read.  Both accumulate in float32 and return
``lhs.dtype`` (bf16 on the serve path, as the operands).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from . import resolve_impl


def _tile(dim: int, cap: int) -> int:
    """``dim`` when it is at most ``cap``, else the largest multiple of 128
    that divides it and is at most ``cap`` (``dim`` when none does)."""
    if dim <= cap:
        return dim
    return next((t for t in range(cap - cap % 128, 0, -128) if dim % t == 0),
                dim)


def gmm_tiling(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn) for ``gmm``, within the TPU's 16 MB of scoped fast
    memory with the weight tile double buffered.

    Decode-sized row counts take row tiles of 64 and weight tiles of up
    to 3M values: a routed group holds a few rows, and a group that
    straddles two row tiles has its weights read twice, so fewer row
    tiles read fewer bytes.  Prefill takes row tiles of 512 and weight
    tiles of up to 1.5M values (at DeepSeek-V2-Lite's widths [1024, 1408]
    for gate and up, [1408, 1024] for down): each row tile reads its
    group's weights again, and the accumulator grows with the row tile.
    """
    tm, cap = (64, 3 << 20) if m <= 1024 else (512, 3 << 19)
    tn = _tile(n, cap >> 10)
    return tm, _tile(k, cap // tn), tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   impl: str = "auto") -> jax.Array:
    """lhs [m, k], rhs [G, k, n], group_sizes int32 [G] -> [m, n]; rows
    past the groups' total are left undefined (zero on ``"ref"``)."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    m, k = lhs.shape
    tm, tk, tn = gmm_tiling(m, k, rhs.shape[2])
    pad = -m % tm
    out = gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, group_sizes,
              preferred_element_type=lhs.dtype, tiling=(tm, tk, tn),
              interpret=impl == "interpret")
    return out[:m]
