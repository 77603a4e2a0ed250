"""Pallas TPU kernels of the paging path, each with a jnp reference (ref.py)
and a dispatching wrapper (ops.py)."""

import jax


def resolve_impl(impl: str) -> str:
    """``"auto"`` is the jnp reference on the CPU and the Pallas kernel on
    every other backend, so a chip run never falls back to the reference."""
    if impl == "auto":
        return "ref" if jax.default_backend() == "cpu" else "pallas"
    return impl
