"""Per-kernel validation: shape/dtype sweeps, interpret=True vs jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.page_gather.kernel import page_gather, page_scatter
from repro.kernels.page_gather.ref import page_gather_ref, page_scatter_ref

TOL = dict(float32=dict(atol=2e-5, rtol=2e-5),
           bfloat16=dict(atol=3e-2, rtol=3e-2))


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------ flash attention


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,h,kvh,sq,sk,d", [
    (1, 4, 4, 32, 32, 16),       # MHA square
    (2, 8, 2, 64, 64, 32),       # GQA
    (1, 4, 1, 48, 80, 64),       # MQA, ragged lengths (padding path)
    (2, 2, 2, 16, 128, 128),     # long KV, wide head
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(dtype, b, h, kvh, sq, sk, d, causal):
    if causal and sq != sk:
        pytest.skip("causal requires aligned q/k positions in this harness")
    q = rand(0, (b, h, sq, d), dtype)
    k = rand(1, (b, kvh, sk, d), dtype)
    v = rand(2, (b, kvh, sk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=32,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_attention_sliding_window():
    b, h, s, d = 1, 2, 64, 16
    q, k, v = (rand(i, (b, h, s, d), "float32") for i in range(3))
    out = flash_attention(q, k, v, causal=True, window=8, block_q=16,
                          block_k=16, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=8)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_block_shape_independence():
    """Block size must never change the result."""
    b, h, s, d = 1, 2, 96, 32
    q, k, v = (rand(i, (b, h, s, d), "float32") for i in range(3))
    outs = [flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in [(16, 16), (32, 48), (96, 96)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------- gather / scatter


@settings(max_examples=20, deadline=None)
@given(
    p=st.integers(4, 32), elems=st.sampled_from([128, 256, 512]),
    seed=st.integers(0, 2**31 - 1),
)
def test_page_gather_property(p, elems, seed):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(p, elems)), jnp.float32)
    n = rng.integers(1, p + 1)
    ids = jnp.asarray(rng.choice(p, size=n, replace=False), jnp.int32)
    out = page_gather(pool, ids, interpret=True)
    np.testing.assert_allclose(out, page_gather_ref(pool, ids))


@settings(max_examples=20, deadline=None)
@given(
    p=st.integers(4, 32), elems=st.sampled_from([128, 256]),
    seed=st.integers(0, 2**31 - 1),
)
def test_page_scatter_property(p, elems, seed):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(p, elems)), jnp.float32)
    n = int(rng.integers(1, p + 1))
    ids = jnp.asarray(rng.choice(p, size=n, replace=False), jnp.int32)
    pages = jnp.asarray(rng.normal(size=(n, elems)), jnp.float32)
    ref = page_scatter_ref(pool, ids, pages)
    out = page_scatter(pool, ids, pages, interpret=True)
    np.testing.assert_allclose(out, ref)


@pytest.mark.parametrize("page_shape", [
    (128,),             # flat page, one row of lanes
    (4, 256),           # 2-D page, viewed as 8 rows
    (2, 16, 64),        # a KV pool page [KVH, ps, D]
])
def test_gather_scatter_roundtrip(page_shape):
    """UFFDIO_COPY semantics: install then read back the exact page, for
    pools of any rank."""
    pool = jnp.zeros((8,) + page_shape, jnp.float32)
    ids = jnp.asarray([3, 5], jnp.int32)
    pages = jnp.asarray(
        np.random.default_rng(0).normal(size=(2,) + page_shape), jnp.float32)
    pool = page_scatter(pool, ids, pages, interpret=True)
    assert pool.shape == (8,) + page_shape
    back = page_gather(pool, ids, interpret=True)
    assert back.shape == (2,) + page_shape
    np.testing.assert_allclose(back, pages)
    np.testing.assert_allclose(pool[1], 0.0)     # untouched slots stay zero


@pytest.mark.parametrize("kernel", ["gather", "scatter"])
def test_page_kernels_refuse_pages_off_the_lane_width(kernel):
    """A page that is not a whole number of 128-lane rows cannot be a TPU
    block: the kernel path raises instead of silently using the reference."""
    pool = jnp.zeros((4, 3, 40), jnp.float32)          # 120 elements/page
    ids = jnp.asarray([1], jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        if kernel == "gather":
            page_gather(pool, ids, interpret=True)
        else:
            page_scatter(pool, ids, jnp.ones((1, 3, 40)), interpret=True)
    # the jnp reference has no such constraint
    np.testing.assert_allclose(page_gather_ref(pool, ids), pool[1:2])
