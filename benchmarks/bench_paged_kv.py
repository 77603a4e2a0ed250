"""UMap-on-TPU transplant benchmarks: paged-KV page-size sweep + weight pager.

(a) Paged-KV page size (tokens/page) — the UMAP_PAGESIZE knob at the KV
    level.  Measured on the XLA gather path (CPU wall time at small scale)
    plus the analytic v5e model both benchmarks in EXPERIMENTS.md read:
    per-token decode traffic = pages/seq · page_bytes, against fragmentation
    waste = (page - len % page) — the same small-vs-large-page tradeoff as
    the paper's Figs 2/7 (faults amortize with big pages; dead data grows).

(b) Memory-efficiency vs the contiguous (mmap-analogue) cache: reserved vs
    used tokens across a zipfian length distribution.

(c) Weight-pager readahead sweep — the UMAP_READ_AHEAD knob for layer
    streaming (paper §3.6 prefetch hints).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.ops import paged_attention
from repro.kvcache.paged_kv import ContiguousKVCache, PagedKVCache, PagedKVConfig
from repro.serve.weight_pager import LayerWeightPager

from .common import Row

# v5e analytic constants
HBM_BW = 819e9


def _sweep_page_size(quick: bool) -> list:
    rows = []
    b, h, kvh, d = 8, 8, 8, 128
    total_kv = 4096                        # logical tokens per sequence
    rng = np.random.default_rng(0)
    lengths = jnp.asarray(rng.integers(total_kv // 2, total_kv, size=b), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    for ps in ([16, 64, 256] if quick else [8, 16, 32, 64, 128, 256, 512]):
        pages_per_seq = total_kv // ps
        pool_pages = b * pages_per_seq
        kp = jnp.asarray(rng.normal(size=(pool_pages, kvh, ps, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(pool_pages, kvh, ps, d)), jnp.float32)
        table = jnp.asarray(
            rng.permutation(pool_pages).reshape(b, pages_per_seq), jnp.int32)
        fn = jax.jit(lambda q, kp, vp, t, l: paged_attention(q, kp, vp, t, l,
                                                             impl="ref"))
        fn(q, kp, vp, table, lengths).block_until_ready()
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            fn(q, kp, vp, table, lengths).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        # analytic v5e: bytes touched per decode step (pool reads dominate)
        page_bytes = ps * kvh * d * 2 * 2          # k+v bf16
        touched = float(jnp.sum((lengths + ps - 1) // ps)) * page_bytes
        frag = float(jnp.sum(ps - 1 - (lengths - 1) % ps)) * kvh * d * 2 * 2
        rows.append(Row("paged_kv_sweep", "umap", ps, dt, {
            "bytes_touched": touched,
            "frag_waste_bytes": frag,
            "v5e_hbm_seconds": touched / HBM_BW,
        }))
    return rows


def _memory_efficiency() -> list:
    rng = np.random.default_rng(1)
    lens = rng.integers(16, 512, size=32)
    out = []
    for ps in (16, 64, 256):
        cfg = PagedKVConfig(num_layers=1, num_kv_heads=8, head_dim=128,
                            page_size=ps, num_pages=int(lens.sum() // ps + 64))
        pc = PagedKVCache(cfg)
        for sid, L in enumerate(lens):
            k = jnp.zeros((1, int(L), 8, 128), jnp.bfloat16)
            pc.add_sequence(sid, k, k)
        reserved = pc.allocator.used_pages * ps
        out.append(Row("paged_kv_memory", "umap", ps, 0.0, {
            "reserved_tokens": int(reserved),
            "used_tokens": int(lens.sum()),
            "utilization": float(lens.sum() / reserved),
        }))
    cc = ContiguousKVCache(1, 8, 128, max_seqs=32, max_len=512)
    for sid, L in enumerate(lens):
        k = jnp.zeros((1, int(L), 8, 128), jnp.bfloat16)
        cc.add_sequence(sid, k, k)
    out.append(Row("paged_kv_memory", "mmap", 512, 0.0, {
        "reserved_tokens": cc.reserved_tokens(),
        "used_tokens": cc.used_tokens(),
        "utilization": cc.used_tokens() / cc.reserved_tokens(),
    }))
    return out


def _weight_pager_sweep(quick: bool) -> list:
    rng = np.random.default_rng(2)
    n_layers = 12
    layers = [{"w": np.asarray(rng.normal(size=(256, 256)), np.float32)}
              for _ in range(n_layers)]
    x = jnp.ones((64, 256), jnp.float32)

    def apply_fn(p, x, i):
        return jnp.tanh(x @ jnp.asarray(p["w"]))

    rows = []
    for ra in ([0, 2] if quick else [0, 1, 2, 4]):
        pager = LayerWeightPager(layers, num_slots=max(2, ra + 2), readahead=ra)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            pager.run(x, apply_fn).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        waits = pager.stats["waits"]
        rows.append(Row("weight_pager", "umap", ra, dt,
                        {"readahead": ra, "waits": waits,
                         "fills": pager.stats["fills"]}))
        pager.close()
    return rows


def run(quick: bool = True) -> list:
    return _sweep_page_size(quick) + _memory_efficiency() + _weight_pager_sweep(quick)
