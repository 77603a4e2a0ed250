"""jit'd public wrappers for bulk page install/evict."""

from __future__ import annotations

from .. import resolve_impl
from .kernel import page_gather as _gather, page_scatter as _scatter
from .ref import page_gather_ref, page_scatter_ref


def page_gather(pool, page_ids, impl="auto"):
    impl = resolve_impl(impl)
    if impl == "pallas":
        return _gather(pool, page_ids)
    if impl == "interpret":
        return _gather(pool, page_ids, interpret=True)
    return page_gather_ref(pool, page_ids)


def page_scatter(pool, page_ids, pages, impl="auto"):
    impl = resolve_impl(impl)
    if impl == "pallas":
        return _scatter(pool, page_ids, pages)
    if impl == "interpret":
        return _scatter(pool, page_ids, pages, interpret=True)
    return page_scatter_ref(pool, page_ids, pages)
