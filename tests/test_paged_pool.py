"""The engine's paged KV format, unit by unit: the stacked pools
``<name>_pool`` [count, P, ps, *row] that ``ServeEngine`` allocates,
``serve.engine._install_pages`` fills from a prefill, and the decode step
appends to (``blocks._write_paged_rows``) and reads back
(``blocks._gather_paged_rows``).

Each geometry is a serve cell's cached row (``blocks.pool_rows``): per-head
K and V of Qwen2-1.5B (KVH 2, D 128) and SmolLM-135M (KVH 3, D 64), and
DeepSeek-V2-Lite's latent row padded to 640.  Pools are small in pages and
filled with random bf16 rows, so every comparison is bitwise: the pool
format moves rows and never rounds them.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.blocks import (
    _gather_paged_rows,
    _write_decode_cache,
    _write_paged_rows,
    pool_rows,
)
from repro.serve.engine import _install_pages, _pool_rows_of

GEOMETRIES = ["qwen2-1.5b", "smollm-135m", "deepseek-v2-lite"]
PS, PAGES, MAXP, COUNT, LAYER = 4, 16, 3, 2, 1
DT = jnp.bfloat16


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(DT)


def _lane_tables(rng, lens):
    """Page tables [B, MAXP] of distinct physical pages >= 1 (page 0 is
    the engine's scratch page), zeroed for an idle lane (length 0)."""
    free = rng.permutation(np.arange(1, PAGES))
    table = np.zeros((len(lens), MAXP), np.int32)
    for b, n in enumerate(lens):
        if n:
            table[b] = free[b * MAXP:(b + 1) * MAXP]
    return table


# lengths before the step's write: mid-page (slot 2 of page 1), page-opening
# (slot 0 of page 2), and a lane left idle beside a live one
CASES = {"mid_page": [6, 2, 10], "new_page": [8, 4], "idle_lane": [0, 5]}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("arch", GEOMETRIES)
def test_append_then_gather_matches_contiguous(arch, case):
    rows_of = pool_rows(get_config(arch))
    names = tuple(rows_of)
    lens = np.asarray(CASES[case], np.int32)
    b, width = len(lens), MAXP * PS
    rng = np.random.default_rng(0)
    table = _lane_tables(rng, lens)

    # the same sequences held contiguously, and in pool pages over noise
    contig = {n: _rand(rng, (b, width) + row) for n, row in rows_of.items()}
    pos = np.arange(width)[None, :]
    contig["pos"] = jnp.asarray(np.where(pos < lens[:, None], pos, -1),
                                jnp.int32)
    pools = {f"{n}_pool": np.array(_rand(rng, (COUNT, PAGES, PS) + row))
             for n, row in rows_of.items()}
    for n in names:
        held = np.asarray(contig[n]).reshape(b, MAXP, PS, *rows_of[n])
        for lane in np.flatnonzero(lens):
            pools[f"{n}_pool"][LAYER, table[lane]] = held[lane]
    cache = {**{k: jnp.asarray(v) for k, v in pools.items()},
             "layer": jnp.int32(LAYER), "table": jnp.asarray(table),
             "len": jnp.asarray(lens)}
    token = {n: _rand(rng, (b, 1) + row) for n, row in rows_of.items()}

    new = _write_paged_rows(cache, token)
    got, kpos, cur = _gather_paged_rows(new, names)
    want = _write_decode_cache(contig, token, jnp.asarray(lens), None)

    after = lens + 1
    np.testing.assert_array_equal(np.asarray(new["len"]), after)
    np.testing.assert_array_equal(np.asarray(cur), lens)
    kpos = np.asarray(kpos)
    assert kpos.shape == (b, width)
    np.testing.assert_array_equal(kpos, np.where(pos < after[:, None], pos, -1))
    for n in names:
        g, w = np.asarray(got[n]), np.asarray(want[n])
        assert g.shape == (b, width) + rows_of[n]
        for lane, n_live in enumerate(after):
            if lens[lane]:
                np.testing.assert_array_equal(g[lane, :n_live], w[lane, :n_live])
        # the pool changed at each lane's (page, slot) of this layer only:
        # other pages and the other layer are as they were, and the idle
        # lane's zeroed table sent its row to scratch page 0, slot 0
        expect = pools[f"{n}_pool"].copy()
        for lane, n_held in enumerate(lens):
            page = table[lane, n_held // PS]
            expect[LAYER, page, n_held % PS] = np.asarray(token[n])[lane, 0]
        np.testing.assert_array_equal(np.asarray(new[f"{n}_pool"]), expect)


@pytest.mark.parametrize("start", ["a0_zero", "shared_boundary"])
@pytest.mark.parametrize("arch", GEOMETRIES)
def test_install_then_gather_roundtrip(arch, start):
    """Prefilled rows [a0, eff) installed into a lane's pages read back,
    from a0 = 0 or from a shared prefix's boundary page as the engine's
    ``_prefill_into_pool`` does on a prefix hit (the pages before a0 hold
    the prefix's rows already)."""
    cfg = get_config(arch)
    rows_of = pool_rows(cfg)
    names = tuple(rows_of)
    rng = np.random.default_rng(1)
    eff, s_cache, lane, b = 10, 16, 1, 2
    pages = [7, 3, 12]                                    # -(-eff // PS) pages
    prefill = {n: _rand(rng, (COUNT, 1, s_cache) + row)
               for n, row in rows_of.items()}
    pools = {f"{n}_pool": _rand(rng, (COUNT, PAGES, PS) + row)
             for n, row in rows_of.items()}
    if start == "a0_zero":
        a0 = 0
    else:
        shared_tok = 6                                    # ends inside page 1
        n_shared = -(-shared_tok // PS)
        a0 = (n_shared - 1) * PS
        for n in names:                                   # the prefix's pages
            held = prefill[n][:, 0, :a0].reshape(COUNT, -1, PS, *rows_of[n])
            pools[f"{n}_pool"] = pools[f"{n}_pool"].at[
                :, jnp.asarray(pages[:a0 // PS])].set(held)
    before = {k: np.asarray(v) for k, v in pools.items()}

    cache = _install_pages(pools, _pool_rows_of(cfg, prefill, a0, eff),
                           pages[a0 // PS:], lane, PS, MAXP)
    table = np.zeros((b, MAXP), np.int32)
    table[lane] = pages
    lens = np.zeros(b, np.int32)
    lens[lane] = eff
    for layer in range(COUNT):
        got, kpos, _ = _gather_paged_rows(
            {**cache, "layer": jnp.int32(layer), "table": jnp.asarray(table),
             "len": jnp.asarray(lens)}, names)
        assert (np.asarray(kpos)[lane] >= 0).sum() == eff
        for n in names:
            np.testing.assert_array_equal(
                np.asarray(got[n])[lane, a0:eff],
                np.asarray(prefill[n])[layer, 0, a0:eff])
            np.testing.assert_array_equal(
                np.asarray(got[n])[lane, :a0],
                np.asarray(prefill[n])[layer, 0, :a0])
    for n in names:                        # no page outside the lane's moved
        other = np.setdiff1d(np.arange(PAGES), pages)
        np.testing.assert_array_equal(
            np.asarray(cache[f"{n}_pool"])[:, other],
            before[f"{n}_pool"][:, other])
