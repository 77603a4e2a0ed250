"""The latent-attention MoE configuration (DeepSeek-V2-Lite) at smoke size
on the CPU: the engine against the plain reference on seeded random
weights, the counts at published sizes, and the new serve cells (correct,
their control not correct, a planted fault not correct)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import cells
import mla_counts
import mla_serving
from harness import merged
from reference import deepseek_v2

DS = "deepseek-v2-lite.longctx_decode"
CHAT = "smollm-135m.chat_short"


def _model(smoke=True):
    model = json.loads((cells.BENCH / "configs" / "deepseek-v2-lite.json")
                       .read_text())
    return merged(model, model["smoke"]) if smoke else model


def test_published_counts():
    arch = mla_serving.arch_of(_model(smoke=False))
    assert mla_serving.param_count(arch) == 2_839_831_040
    assert mla_serving.param_count(dict(arch, num_hidden_layers=27)) \
        == 15_706_484_224
    assert mla_counts.latent_row_bytes(arch, 2) == 5 * 1_152
    assert mla_counts.non_expert_bytes(arch, 2) == 831_046_656
    # 6 routed + 2 shared experts of 8,650,752 per MoE layer, not 64
    assert mla_counts.active_params(arch) == (
        5 * mla_counts.attn_params(arch) + 3 * 2048 * 10944
        + 4 * (2048 * 64 + 8 * mla_counts.expert_params(arch)))
    # per key and head: a 192-wide score and a 128-wide weighted sum
    assert mla_counts.attention_flops(arch, 1) == 2 * 5 * 16 * (192 + 128)


def test_engine_matches_the_reference_through_prefix_cow_and_requeue():
    """Seeded weights in the benchmark layout, mapped to the program (rope
    columns permuted), served with a shared prefix (its boundary page
    copied on write) and a pool small enough to requeue: the engine's
    logits at every served position match the float32 reference's, which
    rotates the published interleaved pairs.  Both sides are float32 here,
    so only the order of summation differs: 2e-3 relative, 2e-4 absolute."""
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    model = _model()
    arch = mla_serving.arch_of(model)
    cfg = mla_serving.program_config(model)
    w = mla_serving.make(arch, 11, jnp.float32)
    eng = ServeEngine(cfg, mla_serving.to_program(arch, dict(w)), EngineConfig(
        max_batch=3, page_size=4, num_pages=16, max_pages_per_seq=8,
        prefill_bucket=8))
    seen = {}
    decode = eng._decode

    def capture(params, caches, tokens, cur):
        logits, out = decode(params, caches, tokens, cur)
        for rid, lane in eng.lane_of.items():
            seen.setdefault(rid, []).append(np.asarray(logits[lane]))
        return logits, out

    eng._decode = capture
    rng = np.random.default_rng(8)
    prefix = rng.integers(0, arch["vocab_size"], 10).astype(np.int32)
    eng.register_prefix(prefix)
    prompts = [prefix if i % 2 else np.concatenate(
        [prefix, rng.integers(0, arch["vocab_size"], 3).astype(np.int32)])
        for i in range(5)]
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=9)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_steps=500)
    st = eng.stats
    assert st["requeues"] >= 1 and st["cow_copies"] >= 2 and st["prefix_hits"]
    assert st["moe_not_computed"] == 0 and st["moe_assignments"] > 0
    for r in reqs:
        seq = np.concatenate([r.prompt, r.generated[:-1]])
        tokens = np.zeros(deepseek_v2.Q_BLOCK, np.int32)
        tokens[:len(seq)] = seq
        h = deepseek_v2.hidden(arch, w, jnp.asarray(tokens))
        ref = np.asarray(h[len(r.prompt) - 1:len(seq)] @ w["lm_head"])
        # a requeued request's logits restart with its restart
        got = np.stack(seen[r.rid][-len(r.generated):])
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("cell", [DS, CHAT])
def test_new_cells_run_correct(cell):
    out = cells.run(cell, seconds=2.0 if cell == CHAT else 0.3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"]["backend_compile_duration"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_traced_mla_run_reports_the_moe_counter():
    out = cells.run(DS, trace=True)
    assert out["correct"], out["checks"]
    # the rooflines read the TPU's program names and peaks, which a CPU
    # run lacks; the counter reads the engine
    hit = out["metrics"]["moe.experts_hit.mla"]["value"]
    assert 0 < hit <= 100
    assert set(out["metrics"]) == {"moe.experts_hit.mla"}


def _row_not_written(orig):
    def write(cache, row):
        out = orig(cache, row)
        return {**out, "ckv_pool": cache["ckv_pool"]}
    return write


@pytest.mark.parametrize("cell,how", [(DS, "fp8"), (CHAT, "fp8"),
                                      (DS, "row_not_written")])
def test_control_and_fault_are_not_correct(cell, how, monkeypatch):
    kw = {"seconds": 2.0 if cell == CHAT else 0.3}
    if how == "fp8":
        kw["options"] = {"control": "fp8"}
    else:
        from repro.models import blocks
        monkeypatch.setattr(blocks, "_write_paged_rows",
                            _row_not_written(blocks._write_paged_rows))
    out = cells.run(cell, **kw)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["worst_gap_spreads"]
    assert gap["value"] > gap["limit"]


def test_routing_margins_sit_beside_every_compared_gap():
    import routing_margins
    out = routing_margins.reading(DS, cells.SEED, 0.3, smoke=True,
                                  require_chip=False)
    assert out["correct"] and out["positions"] > 0
    assert out["margin_at_worst"] >= 0
    under = out["positions_with_margin_under"]
    assert under["0.0001"] <= under["0.001"] <= under["0.01"] <= out["positions"]
