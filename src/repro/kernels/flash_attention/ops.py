"""jit'd public wrapper: Pallas on an accelerator, the jnp oracle on the CPU."""

from __future__ import annotations

from .. import resolve_impl
from .kernel import flash_attention as _pallas
from .ref import flash_attention_ref


def flash_attention(q, k, v, *, causal=True, window=None, impl="auto", **kw):
    """impl: 'pallas' | 'interpret' | 'ref' | 'auto' (see ``resolve_impl``)."""
    impl = resolve_impl(impl)
    if impl == "pallas":
        return _pallas(q, k, v, causal=causal, window=window, **kw)
    if impl == "interpret":
        return _pallas(q, k, v, causal=causal, window=window, interpret=True, **kw)
    return flash_attention_ref(q, k, v, causal=causal, window=window)
