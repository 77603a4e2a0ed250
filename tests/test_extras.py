"""Supplementary coverage: stub-frontend decode, hints math, elastic report,
paged-vs-contiguous model parity, loss chunking invariance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models as M
from repro.configs.registry import get_smoke_config
from repro.core.hints import (
    PageSizeAdvisor,
    StoreProfile,
    WorkloadProfile,
    bandwidth_delay_pages,
    plan_prefetch,
)
from repro.train.loss import chunked_cross_entropy


def test_vlm_embeds_prefill_then_token_decode():
    """VLM: prefill on patch embeddings, then decode text tokens (M-RoPE)."""
    cfg = get_smoke_config("qwen2-vl-7b")
    params = M.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    B, S = 2, 12
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, 3, S)).copy()
    batch = {"embeds": jnp.asarray(rng.normal(size=(B, S, cfg.d_model)),
                                   jnp.float32),
             "positions": jnp.asarray(pos)}
    cache = M.init_cache(cfg, B, S + 8)
    _, cache = M.prefill(cfg, params, batch, cache)
    toks = jnp.asarray([3, 7], jnp.int32)
    logits, cache = M.decode_step(cfg, params, cache, toks,
                                  jnp.full((B,), S, jnp.int32))
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits[:, : cfg.vocab_size])).all()
    # a second step continues coherently
    nxt = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
    logits2, _ = M.decode_step(cfg, params, cache, nxt,
                               jnp.full((B,), S + 1, jnp.int32))
    assert np.isfinite(np.asarray(logits2[:, : cfg.vocab_size])).all()


def test_encdec_embeds_decode_consistency():
    """seamless: decode with cached cross-KV matches full forward."""
    cfg = get_smoke_config("seamless-m4t-medium")
    params = M.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(1)
    B, S, Sm = 2, 10, 7
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "src_embeds": jnp.asarray(rng.normal(size=(B, Sm, cfg.d_model)),
                                  jnp.float32),
    }
    hid, _ = M.forward_train(cfg, params, batch)
    ref = M.lm_logits(cfg, params, hid)[:, -1]
    cache = M.init_cache(cfg, B, S + 4, memory_len=Sm)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :-1]
    _, cache = M.prefill(cfg, params, pre, cache)
    logits, _ = M.decode_step(cfg, params, cache, batch["tokens"][:, -1],
                              jnp.full((B,), S - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-3, atol=2e-4)


def test_page_size_advisor_tradeoffs():
    """Random workloads prefer small pages; sequential prefer large (§3.6)."""
    nvme = StoreProfile.nvme()
    random_wl = WorkloadProfile(useful_bytes_per_access=256, locality_bytes=256)
    seq_wl = WorkloadProfile(useful_bytes_per_access=256,
                             locality_bytes=8 << 20)
    assert PageSizeAdvisor(nvme, random_wl).recommend() <= 64 * 1024
    assert PageSizeAdvisor(nvme, seq_wl).recommend() >= 1 << 20
    # HDD-latency store pushes the optimum up even for modest locality
    hdd = StoreProfile.lustre_hdd()
    mid_wl = WorkloadProfile(useful_bytes_per_access=256,
                             locality_bytes=64 * 1024)
    assert (PageSizeAdvisor(hdd, mid_wl).recommend()
            >= PageSizeAdvisor(nvme, mid_wl).recommend())


def test_bandwidth_delay_filler_sizing():
    nvme = StoreProfile.nvme()
    small = bandwidth_delay_pages(nvme, 4096)
    large = bandwidth_delay_pages(nvme, 8 << 20)
    assert small > large >= 1     # paper §6.1: fewer fillers at big pages


def test_plan_prefetch_dedup_and_order():
    offs = [10, 5000, 20, 9000, 4097]
    plan = plan_prefetch(offs, page_size=4096, max_pages=3)
    assert plan == [0, 1, 2]


def test_chunked_xent_invariant_to_chunk_size():
    cfg = get_smoke_config("smollm-135m")
    params = M.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(2)
    hid = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24)), jnp.int32)
    losses = [float(chunked_cross_entropy(cfg, params, hid, labels,
                                          chunk=c)[0])
              for c in (4, 8, 24, 64)]
    np.testing.assert_allclose(losses, losses[0], rtol=1e-6)


# ------------------------------------------------------ MoE dispatch laws


def test_moe_dispatch_invariants_property():
    """Capacity respected; each kept assignment contributes exactly once;
    unrouted experts produce zero-padded slots (hypothesis over shapes/keys)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.models.moe import _moe_forward_dense, moe_param_specs
    from repro.models.common import init_param_tree

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           t=st.integers(4, 24), e=st.sampled_from([2, 4, 8]),
           k=st.sampled_from([1, 2]),
           cf=st.sampled_from([0.5, 1.0, 4.0]))
    def run(seed, t, e, k, cf):
        if k > e:
            return
        d, ff = 8, 16
        p = init_param_tree(moe_param_specs(d, ff, e, "tp"),
                            jax.random.key(seed % 1000), jnp.float32)
        x = jax.random.normal(jax.random.key(seed), (1, t, d), jnp.float32)
        y, aux = _moe_forward_dense(p, x, k, cf)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert 0.0 <= float(aux["moe_drop_frac"]) <= 1.0
        # capacity for every token at every expert -> nothing dropped (an
        # expert takes at most one assignment per token; cf alone does not
        # bound that: t=4, e=8, k=1, cf=4 gives 2 slots per expert)
        if max(1, round(t * k / e * cf)) >= t:
            assert float(aux["moe_drop_frac"]) == 0.0
        # zero input -> zero output (experts are linear+silu in x)
        y0, _ = _moe_forward_dense(p, jnp.zeros_like(x), k, cf)
        np.testing.assert_allclose(np.asarray(y0), 0.0, atol=1e-6)

    run()
