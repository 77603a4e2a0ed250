"""The paged latent-attention reader of MLA decode on the CPU: the Pallas
kernel (``"interpret"``), which visits only each lane's live pages,
against the whole-table gather (``"ref"``), at the smoke model's widths
and at DeepSeek-V2-Lite's; the engine serving the smoke model through
either; and the engine's count of the pages its decode reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models as M
from repro.configs.registry import get_smoke_config
from repro.kernels import paged_latent_attention as pla
from repro.serve.engine import EngineConfig, Request, ServeEngine
from test_serve_engine import (
    assert_served_logits_match_forward,
    serve_capturing_logits,
)

# (heads, row width, page size, pool dtype, table width): each table holds
# two whole blocks of pages and a partial third
GEOMETRIES = {
    "smoke": (4, 128, 8, jnp.float32, 150),
    "deepseek-v2-lite": (16, 640, 16, jnp.bfloat16, 80),
}
CASES = ("lengths", "inactive_lane", "shuffled_pages", "layer_1",
         "partial_last_block", "window")


def _lens_and_table(case, ps, maxp, pages, rng):
    """Post-write lengths and page tables of one case's lanes."""
    bk = pla.pages_per_block(ps, maxp) * ps
    full = maxp * ps
    if case in ("lengths", "layer_1"):
        lens = [1, ps, ps + 1, bk, bk + 1, full]
    elif case == "inactive_lane":
        lens = [1, 3 * ps + 2, 1]
    elif case == "partial_last_block":
        lens = [2 * bk + ps + 3, full - 1]
    else:
        lens = [full, bk - 1, 5 * ps]
    table = np.zeros((len(lens), maxp), np.int32)
    used = rng.permutation(np.arange(1, pages))      # page 0 is scratch
    for b, n in enumerate(lens):
        if case == "inactive_lane" and n == 1:
            continue                        # an idle lane: its scratch page
        live = -(-n // ps)
        if case in ("shuffled_pages", "window"):
            table[b, :live] = used[b * maxp:b * maxp + live]
        else:                               # consecutive runs of pages
            table[b, :live] = 1 + b * maxp + np.arange(live)
    return np.asarray(lens, np.int32), table


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_live_pages_kernel_matches_whole_table_gather(geometry, case):
    heads, width, ps, dtype, maxp = GEOMETRIES[geometry]
    rng = np.random.default_rng([len(geometry), CASES.index(case)])
    count, layer = (3, 1) if case == "layer_1" else (1, 0)
    lens, table = _lens_and_table(case, ps, maxp, 6 * maxp + 1, rng)
    kp, kq = jax.random.split(jax.random.key(int(rng.integers(1 << 30))))
    pool = jax.random.normal(kp, (count, 6 * maxp + 1, ps, width),
                             jnp.float32).astype(dtype)
    qc = 3 * jax.random.normal(kq, (len(lens), heads, width), jnp.float32)
    window = 3 * ps + 5 if case == "window" else None
    args = (qc, pool, jnp.int32(layer), jnp.asarray(table),
            jnp.asarray(lens), 0.07, window)
    got = pla.paged_latent_attention(*args, impl="interpret")
    want = pla.paged_latent_attention(*args, impl="ref")
    assert got.shape == want.shape == qc.shape and got.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)


@pytest.fixture(scope="module")
def mla_model():
    cfg = get_smoke_config("deepseek-v2-lite")
    return cfg, M.init_params(cfg, jax.random.key(5))


@pytest.fixture
def live_reader(monkeypatch):
    """The kernel in interpret mode where the CPU would take the gather."""
    monkeypatch.setattr(pla, "resolve_impl",
                        lambda impl: "interpret" if impl == "auto" else impl)


ECFG = EngineConfig(max_batch=2, page_size=8, num_pages=24,
                    max_pages_per_seq=6, prefill_bucket=8)


def test_engine_decodes_the_same_tokens_with_the_kernel(mla_model,
                                                        monkeypatch):
    """The engine serving DeepSeek's smoke model reads its latent pages
    through the kernel (live pages only) as through the gather (the whole
    table): the same tokens, logits within float32 rounding, and both
    match the full forward."""
    cfg, params = mla_model
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (14, 7, 17)]
    served = {}
    for how in ("gather", "kernel"):
        if how == "kernel":
            monkeypatch.setattr(pla, "resolve_impl", lambda impl: "interpret")
        eng, reqs, seen = serve_capturing_logits(cfg, params, ECFG,
                                                 prompts, 12)
        assert eng._reads_live_pages == (how == "kernel")
        assert_served_logits_match_forward(cfg, params, reqs, seen)
        served[how] = ([list(r.generated) for r in reqs], seen)
    assert served["kernel"][0] == served["gather"][0]
    for rid, logits in served["kernel"][1].items():
        np.testing.assert_allclose(np.stack(logits),
                                   np.stack(served["gather"][1][rid]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "smollm-135m"])
def test_decode_counts_the_pages_it_reads(arch, request, tmp_path):
    """Each decode step adds, over the attention layers, the pages its
    reader visits: Σ ceil(len / ps) over the active lanes for MLA's
    live-pages kernel, every table page of them for the gather; the
    ``umap.engine.decode`` span carries the step's two counts."""
    from repro.telemetry import spans

    if arch == "deepseek-v2-lite":
        cfg, params = request.getfixturevalue("mla_model")
        request.getfixturevalue("live_reader")
    else:
        cfg = get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, ECFG)
    layers = sum(s.count for s in cfg.decoder_plan() if s.has_attention)
    rng = np.random.default_rng(4)
    for rid, n in enumerate((9, 4, 20)):
        eng.submit(Request(rid=rid, max_new_tokens=5, prompt=rng.integers(
            1, cfg.vocab_size, n).astype(np.int32)))
    want_read = want_table = 0
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while eng.waiting or eng.active:
            eng._try_admit()
            # post-write lengths of the lanes the next step decodes
            lens = [eng.seq_len[r] + 1 for r in eng.active]
            want_table += len(lens) * ECFG.max_pages_per_seq * layers
            want_read += layers * (sum(-(-n // 8) for n in lens)
                                   if arch == "deepseek-v2-lite" else
                                   len(lens) * ECFG.max_pages_per_seq)
            eng.step()
    finally:
        jax.profiler.stop_trace()
    st = eng.stats
    assert (st["decode_pages_read"], st["decode_table_pages"]) \
        == (want_read, want_table)
    if arch == "deepseek-v2-lite":
        assert 0 < want_read < want_table
    decodes = [r.counts for r in spans.session()
               if r.name == "umap.engine.decode"]
    assert len(decodes) == st["steps"]
    for key in ("decode_pages_read", "decode_table_pages"):
        assert sum(c[key] for c in decodes) == st[key]
