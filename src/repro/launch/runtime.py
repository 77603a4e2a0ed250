"""Process set-up shared by the entry points (``chip_smoke.py``,
``launch/serve.py``, ``launch/train.py``): the persistent compilation
cache and the line that names the device a run used."""

from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, because the cache directory is part of
# what a later process must name to find the entries again.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_cache_events: collections.Counter = collections.Counter()
_listening = False


def _count_cache_event(event: str, **_kw) -> None:
    if event.startswith("/jax/compilation_cache/"):
        _cache_events[event.rsplit("/", 1)[-1]] += 1


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting
    and is left alone; otherwise the cache lives at ``<repo>/.jax_cache``.
    Returns the directory in use."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count_cache_event)
        _listening = True
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def compile_cache_stats() -> dict:
    """Persistent-cache reads that hit, and entries written (JAX counts a
    write as a miss), since ``enable_compile_cache``."""
    return {"hits": _cache_events["cache_hits"],
            "writes": _cache_events["cache_misses"]}


def device_summary() -> dict:
    """The device JAX runs on, as a benchmark or smoke result names it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
