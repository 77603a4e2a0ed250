"""DeepSeek-V2's block at smoke size on the CPU: latent attention's three
paths (decompressed prefill, absorbed decode through a contiguous cache and
through the engine's page pool) agree with the full forward, yarn follows
its published formula, and the dropless MoE computes every routed row.
The comparison with the plain reference (bench/reference/deepseek_v2.py)
is in bench/tests/test_bench_mla.py."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models as M
from repro.configs.registry import get_config, get_smoke_config
from repro.models.blocks import mla_softmax_scale
from repro.models.layers import yarn_freqs
from repro.models.moe import MOE_STATS, moe_dropless, moe_forward
from repro.serve.engine import EngineConfig
from repro.telemetry.collectors import ServeCollector
from test_serve_engine import (
    assert_served_logits_match_forward,
    serve_capturing_logits,
)

ARCH = "deepseek-v2-lite"


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config(ARCH)
    return cfg, M.init_params(cfg, jax.random.key(3))


def _logits(cfg, params, seq):
    hid, _ = M.forward_train(cfg, params, {"tokens": jnp.asarray([seq])})
    return np.asarray(M.lm_logits(cfg, params, hid)[0])


def test_published_plan_and_sizes():
    cfg = get_config(ARCH)
    assert [(s.kind, s.count) for s in cfg.layer_plan()] == [("dense", 1),
                                                             ("moe", 26)]
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.expert_d_ff, cfg.num_experts, cfg.top_k,
            cfg.num_shared_experts) == (512, 128, 64, 128, 1408, 64, 6, 2)
    assert not cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0


def test_yarn_follows_the_published_formula():
    """modeling_deepseek's yarn at DeepSeek-V2-Lite's settings: the ramp
    runs between rotary dims 10 and 23 of 32, and the softmax scale is
    mscale(40, 0.707)^2 / sqrt(192) = 0.114721."""
    cfg = get_config(ARCH)
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    factor, orig, fast, slow = cfg.rope_yarn[:4]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr(fast)), math.ceil(corr(slow))
    assert (low, high) == (10, 23)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / factor * ramp + extra * (1 - ramp)
    got = np.asarray(yarn_freqs(dim, base, cfg.rope_yarn), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / factor, rtol=1e-6)
    assert mla_softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)


def test_absorbed_decode_matches_the_decompressed_forward(model):
    """Prefill (decompressed K and V) then absorbed decode through a
    contiguous latent cache gives the full forward's logits at every
    decoded position (float32; 2e-4 absolute / 2e-3 relative covers the
    different summation order of the absorbed products)."""
    cfg, params = model
    rng = np.random.default_rng(0)
    B, S, n = 2, 13, 5
    seq = rng.integers(0, cfg.vocab_size, (B, S + n)).astype(np.int32)
    cache = M.init_cache(cfg, B, S + n + 3)
    _, cache = M.prefill(cfg, params, {"tokens": jnp.asarray(seq[:, :S])},
                         cache)
    for i in range(n):
        pos = S + i
        logits, cache = M.decode_step(cfg, params, cache,
                                      jnp.asarray(seq[:, pos]),
                                      jnp.full((B,), pos, jnp.int32))
        for b in range(B):
            ref = _logits(cfg, params, seq[b, :pos + 1])[-1]
            np.testing.assert_allclose(np.asarray(logits[b]), ref,
                                       rtol=2e-3, atol=2e-4)


def test_engine_pages_latent_rows_and_counts_moe(model):
    """The engine's MLA segments hold one stacked latent pool each; its
    decode logits at every served position match the full forward, and
    the MoE counters count every routed assignment, none left out."""
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (11, 7, 9)]
    eng, reqs, seen = serve_capturing_logits(
        cfg, params, EngineConfig(max_batch=2, page_size=4, num_pages=24,
                                  max_pages_per_seq=8, prefill_bucket=8),
        prompts, 6)
    assert {k for c in eng._caches for k in c if k.endswith("_pool")} \
        == {"ckv_pool"}
    assert_served_logits_match_forward(cfg, params, reqs, seen)
    st = eng.stats
    moe_layers = cfg.num_layers - cfg.first_k_dense
    buckets = sum(-(-(len(r.prompt) - 1) // 8) * 8 for r in reqs)
    # every lane of every decode step and every bucketed prefill position
    assert st["moe_assignments"] == cfg.top_k * moe_layers * (
        buckets + 2 * st["steps"])
    assert st["moe_not_computed"] == 0
    assert 0 < st["moe_decode_experts_hit"] <= (
        st["steps"] * moe_layers * cfg.num_experts)
    fams = {f.name: f for f in ServeCollector(engine=eng).collect()}
    for key in ("moe_assignments", "moe_not_computed",
                "moe_decode_experts_hit"):
        assert fams[f"umap_serve_{key}_total"].samples[0][2] == st[key]


def _every_expert(p, x, top_k, norm):
    """Every expert on every token, weighted by the router's top-k weights
    (zero elsewhere): the definition the dropless dispatch must equal."""
    x2 = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(x2 @ p["router"], axis=-1)
    w, i = jax.lax.top_k(probs, top_k)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    dense = jnp.zeros_like(probs).at[jnp.arange(x2.shape[0])[:, None], i].set(w)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x2, p["w_gate"])) * \
        jnp.einsum("td,edf->tef", x2, p["w_up"])
    y = jnp.einsum("tef,efd,te->td", h, p["w_down"], dense)
    return y.reshape(x.shape)


@pytest.mark.parametrize("norm", [False, True])
def test_dropless_moe_computes_every_routed_row(norm):
    """Under a router skewed so that two experts take most tokens (a 1.25
    capacity would drop many), the dropless dispatch equals every expert
    weighted by the router, and counts no assignment left out."""
    E, K, d, f, T = 8, 3, 16, 8, 40
    ks = jax.random.split(jax.random.key(5), 5)
    router = jax.random.normal(ks[0], (d, E)) * 0.1
    router = router.at[:, :2].add(1.0)       # tokens with x.sum() > 0 pile up
    p = {"router": router,
         "w_gate": jax.random.normal(ks[1], (E, d, f)) / 4,
         "w_up": jax.random.normal(ks[2], (E, d, f)) / 4,
         "w_down": jax.random.normal(ks[3], (E, f, d)) / 3}
    x = jnp.abs(jax.random.normal(ks[4], (2, T // 2, d)))
    y, stats = moe_dropless(p, x, K, norm_topk_prob=norm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        _every_expert(p, x, K, norm)), rtol=1e-5, atol=1e-5)
    stats = dict(zip(MOE_STATS, np.asarray(stats).tolist()))
    assert stats["assignments"] == T * K and stats["not_computed"] == 0
    _, aux = moe_forward(p, x, K, capacity_factor=1.25, norm_topk_prob=norm)
    assert float(aux["moe_drop_frac"]) > 0.2, "the skew must overflow 1.25"


def _stacked_moe(count, E, d, f, key):
    ks = jax.random.split(key, 4)
    return {"router": jax.random.normal(ks[0], (count, d, E)),
            "w_gate": jax.random.normal(ks[1], (count, E, d, f)) / 4,
            "w_up": jax.random.normal(ks[2], (count, E, d, f)) / 4,
            "w_down": jax.random.normal(ks[3], (count, E, f, d)) / 3}


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("tokens,top_k,routing", [
    (32, 6, "even"),       # a decode step: 32 lanes x top-6 = 192 rows
    (512, 6, "even"),      # a prefill bucket: 3,072 rows
    (192, 1, "one"),       # 192 rows on one expert, the other 63 empty
    (32, 6, "one"),        # every token on the same six experts
])
@pytest.mark.parametrize("layer", [0, 3])
def test_dropless_moe_reads_the_stacked_experts_in_place(impl, tokens, top_k,
                                                          routing, layer):
    """Given a segment's stacked experts [count, E, k, n] and a layer
    index, the dropless dispatch (the grouped matmul over the stack's
    [count x E, k, n] view, every other layer's groups empty) equals the
    dispatch on that layer's experts sliced out, with the same counters."""
    count, E, d, f = 4, 64, 32, 16
    p = _stacked_moe(count, E, d, f, jax.random.key(7))
    if routing == "one":
        p["router"] = p["router"] * 0.01
        p["router"] = p["router"].at[:, :, 5:5 + top_k].add(10.0)
    x = jnp.abs(jax.random.normal(jax.random.key(8), (2, tokens // 2, d)))
    layer_p = jax.tree.map(lambda a: a[layer], p)
    want, want_stats = moe_dropless(layer_p, x, top_k, impl="ref")
    p_in_place = {**p, "router": p["router"][layer]}
    got, stats = jax.jit(moe_dropless, static_argnums=(2,),
                         static_argnames="impl")(
        p_in_place, x, top_k, layer=jnp.int32(layer), impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    if routing == "one":
        assert dict(zip(MOE_STATS, np.asarray(stats).tolist()))[
            "experts_hit"] == top_k


def test_engine_decodes_the_same_tokens_as_with_sliced_experts(model,
                                                               monkeypatch):
    """The engine serving DeepSeek's smoke model reads each MoE layer's
    experts from the stack in place; scanning them as sliced per-layer
    weights instead (the layer scan's earlier form) serves the same
    tokens, and no routed assignment is left uncomputed either way."""
    from repro.models import transformer as T

    cfg, params = model
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 6, 13)]
    ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=24,
                        max_pages_per_seq=8, prefill_bucket=8)
    served = {}
    for how in ("in place", "sliced"):
        if how == "sliced":
            monkeypatch.setattr(T, "_routed_experts",
                                lambda cfg, seg, p, mode: (p, None))
        eng, reqs, seen = serve_capturing_logits(cfg, params, ecfg,
                                                 prompts, 5)
        assert eng.stats["moe_not_computed"] == 0
        served[how] = ([list(r.generated) for r in reqs], seen)
    assert served["in place"][0] == served["sliced"][0]
    for rid, logits in served["in place"][1].items():
        np.testing.assert_allclose(np.stack(logits),
                                   np.stack(served["sliced"][1][rid]),
                                   rtol=1e-5, atol=1e-5)
