"""jit'd public wrapper for paged decode attention."""

from __future__ import annotations

from .. import resolve_impl
from .kernel import paged_attention as _pallas
from .ref import paged_attention_ref


def paged_attention(q, k_pool, v_pool, page_table, lengths, impl="auto"):
    impl = resolve_impl(impl)
    if impl == "pallas":
        return _pallas(q, k_pool, v_pool, page_table, lengths)
    if impl == "interpret":
        return _pallas(q, k_pool, v_pool, page_table, lengths, interpret=True)
    return paged_attention_ref(q, k_pool, v_pool, page_table, lengths)
