"""Fault-injected serving harness: multi-tenant engine under churn.

Every test drives the REAL engine (smoke-config model, jitted decode) through
injected faults — pool exhaustion mid-decode, deadline storms, fair-share
watermark crossings, prefix divergence — and asserts the two properties the
serving layer must never lose (DESIGN.md §16):

  * nothing is lost: every submitted request retires exactly once (finished
    or expired), pages drain back to the free list;
  * determinism: greedy decode through eviction/requeue/COW produces the
    same bytes a sequential single-tenant reference produces.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.models as M
from repro.configs.registry import get_smoke_config
from repro.core import HostArrayStore, TieredStore, UMapConfig, umap, uunmap
from repro.serve.engine import EngineConfig, Request, ServeEngine, Tenant
from repro.telemetry import TelemetryRegistry


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("smollm-135m")
    params = M.init_params(cfg, jax.random.key(0))
    return cfg, params


def reference_generate(cfg, params, prompt, max_new_tokens):
    """Sequential single-request greedy decode (contiguous cache)."""
    toks = list(prompt)
    cache = M.init_cache(cfg, 1, 96)
    batch = {"tokens": jnp.asarray([toks[:-1]], jnp.int32)}
    _, cache = M.prefill(cfg, params, batch, cache)
    out = []
    cur = len(toks) - 1
    for _ in range(max_new_tokens):
        logits, cache = M.decode_step(
            cfg, params, cache, jnp.asarray([toks[-1]], jnp.int32),
            jnp.asarray([cur], jnp.int32))
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
        toks.append(nxt)
        cur += 1
    return out


def assert_none_lost(eng, submitted):
    """Every submitted request retired exactly once; pool fully drained
    (scratch + registered prefixes are the only pages left)."""
    assert not eng.waiting and not eng.active
    assert len(eng.finished) == len(submitted)
    assert {r.rid for r in eng.finished} == {r.rid for r in submitted}
    prefix_pages = sum(len(e.pages) for e in eng._prefixes.values())
    assert eng.allocator.used_pages == 1 + prefix_pages


# ------------------------------------------------- live-mutation regression


def test_adjacent_lanes_boundary_under_exhaustion(model):
    """Regression for the `live.remove(rid)` while iterating bug: two
    adjacent lanes cross a page boundary in the same step with the pool
    exhausted.  The pre-fix loop skipped the lane after the evicted one, so
    its boundary page was silently never allocated and its generation
    diverged after the eventual requeue.  Post-fix: every request still
    finishes with byte-identical output and correct page accounting."""
    cfg, params = model
    ps = 4
    # per request: ceil((4+1)/4)+1 = 3 pages; scratch + 2*3 = 7 fills the
    # pool exactly, so the first same-step double boundary crossing faults
    ecfg = EngineConfig(max_batch=2, page_size=ps, num_pages=7,
                        max_pages_per_seq=8, prefill_bucket=8,
                        prefix_sharing=False)
    eng = ServeEngine(cfg, params, ecfg)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
                    max_new_tokens=10) for i in range(2)]
    for r in reqs:
        eng.submit(r)

    # drive by hand and check the allocation invariant after every step:
    # every live lane's next write position is backed by an allocated page
    # (the bug left the skipped lane's table one page short)
    for _ in range(200):
        if not eng.waiting and not eng.active:
            break
        eng.step()
        for rid in eng.active:
            pos = eng.seq_len[rid]
            assert len(eng.allocator.pages_of(rid)) > pos // ps, \
                f"lane of rid {rid} missed its boundary page allocation"
    assert_none_lost(eng, reqs)
    assert eng.stats["evictions"] >= 1, "scenario must actually exhaust"
    for r in reqs:
        assert r.generated == reference_generate(cfg, params, r.prompt, 10), \
            f"rid {r.rid} diverged after eviction/requeue"


# ------------------------------------------------------------ deadline storm


def test_deadline_storm_requeue_churn(model):
    """Every request under an impossible-deadline storm finishes or is
    requeued — none lost — and restarts are bounded by max_restarts."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=4, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8,
                        max_restarts=3, slo_admission=False)
    eng = ServeEngine(cfg, params, ecfg)
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(8):
        p = rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
        # half the storm can never meet its deadline (already expired)
        dl = -1.0 if i % 2 else None
        reqs.append(Request(rid=i, prompt=p, max_new_tokens=4, deadline_s=dl))
        eng.submit(reqs[-1])
    eng.run_until_drained(max_steps=500)
    assert_none_lost(eng, reqs)
    assert eng.stats["requeues"] >= 1
    for r in reqs:
        assert r.restarts <= ecfg.max_restarts
        if r.deadline_s is None:
            assert not r.expired and r.done
        else:
            # impossible deadline: bounded restarts, then expired (not lost)
            assert r.expired and r.restarts == ecfg.max_restarts
            assert r.slo_miss
    assert eng.stats["expired"] == sum(1 for r in reqs if r.expired)


# ------------------------------------------------- watermark gate hysteresis


def test_global_watermark_hysteresis(model):
    """Admission pauses at high water and stays paused until occupancy
    drops below LOW water — crossing back above low alone must not flap."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=20,
                        max_pages_per_seq=8, admit_high_water=0.5,
                        admit_low_water=0.25)
    eng = ServeEngine(cfg, params, ecfg)
    a = eng.allocator
    a.alloc(99, 9)                       # occupancy 10/20 = 0.5 >= high
    assert not eng._watermark_gate()
    assert eng.stats["admission_pauses"] == 1
    a.free_prefix(99, 4)                 # 6/20 = 0.3: above low, stays paused
    assert not eng._watermark_gate()
    a.free_prefix(99, 2)                 # 4/20 = 0.2 < low: resumes
    assert eng._watermark_gate()
    assert eng.stats["admission_pauses"] == 1, "resume must not re-count"


def test_tenant_fair_share_gate_hysteresis(model):
    """Per-tenant gate: a tenant crossing HIGH water of its fair share is
    paused (counted per-tenant) without pausing the other tenant, and
    resumes only below LOW water of its share."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=4, page_size=4, num_pages=17,
                        max_pages_per_seq=8, admit_high_water=0.85,
                        admit_low_water=0.60)
    eng = ServeEngine(cfg, params, ecfg)
    eng.add_tenant(Tenant("a", weight=1.0))
    eng.add_tenant(Tenant("b", weight=1.0))
    # 16 shareable pages, equal weights (default tenant included): a's fair
    # share comes from fair_shares; consume pages as a's live sequence
    share = eng._fair_share_pages()["a"]
    rid = 1
    eng.active[rid] = Request(rid=rid, prompt=np.arange(2, dtype=np.int32),
                              tenant="a")
    high = int(np.ceil(ecfg.admit_high_water * share))
    eng.allocator.alloc(rid, high)
    assert not eng._tenant_gate("a"), "tenant a must pause at high water"
    assert eng._tenant_gate("b"), "tenant b unaffected by a's pressure"
    assert eng.stats["per_tenant"]["a"]["admission_pauses"] == 1
    assert eng.stats["per_tenant"]["b"]["admission_pauses"] == 0
    # drop between low and high: hysteresis holds the pause
    between = int(np.ceil(ecfg.admit_low_water * share))
    eng.allocator.free_prefix(rid, high - between)
    assert not eng._tenant_gate("a")
    # below low water: resumes, counter unchanged
    eng.allocator.free_prefix(rid, 1)
    assert eng._tenant_gate("a")
    assert eng.stats["per_tenant"]["a"]["admission_pauses"] == 1


# ------------------------------------------------------ multi-tenant storm


def test_multi_tenant_storm_byte_identical(model):
    """Seeded 3-tenant storm under pool pressure: generations are
    byte-identical to a sequential single-tenant reference run, across
    admission reordering, victim eviction, requeues, and COW sharing."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=4, page_size=4, num_pages=48,
                        max_pages_per_seq=16, prefill_bucket=8)
    eng = ServeEngine(cfg, params, ecfg)
    eng.add_tenant(Tenant("gold", weight=4.0, priority=2))
    eng.add_tenant(Tenant("silver", weight=2.0, priority=1))
    eng.add_tenant(Tenant("bronze", weight=1.0, priority=0))
    rng = np.random.default_rng(11)
    prefix = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    eng.register_prefix(prefix, tenant="gold")
    reqs = []
    for i in range(12):
        tenant = ("gold", "silver", "bronze")[i % 3]
        if i % 2:
            p = np.concatenate(
                [prefix, rng.integers(1, cfg.vocab_size, 3).astype(np.int32)])
        else:
            p = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        reqs.append(Request(rid=i, prompt=p, max_new_tokens=5, tenant=tenant))
        eng.submit(reqs[-1])
    eng.run_until_drained(max_steps=1000)
    assert_none_lost(eng, reqs)
    assert eng.stats["prefix_hits"] >= 1
    for r in reqs:
        ref = reference_generate(cfg, params, r.prompt, 5)
        assert r.generated == ref, f"rid {r.rid} ({r.tenant}) diverged"
    # per-tenant accounting closes against the aggregate
    per = eng.stats["per_tenant"]
    assert sum(t["finished"] for t in per.values()) == len(reqs)
    assert sum(t["tokens_generated"] for t in per.values()) == 5 * len(reqs)


# ------------------------------------------------------- prefix COW sharing


def test_prefix_sharing_saves_pages_and_matches_no_sharing(model):
    """COW prefix sharing reduces peak pool pages while generating the
    exact bytes a no-sharing engine generates."""
    cfg, params = model

    def run(sharing):
        ecfg = EngineConfig(max_batch=4, page_size=4, num_pages=96,
                            max_pages_per_seq=16, prefill_bucket=8,
                            prefix_sharing=sharing)
        eng = ServeEngine(cfg, params, ecfg)
        rng = np.random.default_rng(5)
        # deliberately NOT page-aligned (10 % 4 != 0) so the prefill tail
        # rewrites the boundary page (alloc-side COW); prompts equal to the
        # prefix make the first decode write land in a shared page
        # (device-copy COW)
        prefix = rng.integers(1, cfg.vocab_size, 10).astype(np.int32)
        if sharing:
            eng.register_prefix(prefix)
        reqs = []
        for i in range(8):
            p = prefix if i % 2 else np.concatenate(
                [prefix, rng.integers(1, cfg.vocab_size, 3).astype(np.int32)])
            reqs.append(Request(rid=i, prompt=p.copy(), max_new_tokens=4))
            eng.submit(reqs[-1])
        eng.run_until_drained(max_steps=500)
        assert_none_lost(eng, reqs)
        return eng, [r.generated for r in sorted(reqs, key=lambda r: r.rid)]

    shared_eng, shared_gen = run(True)
    plain_eng, plain_gen = run(False)
    assert shared_gen == plain_gen, "sharing changed generated bytes"
    assert shared_eng.stats["prefix_hits"] == 8
    assert shared_eng.stats["shared_pages_mapped"] > 0
    assert shared_eng.stats["cow_copies"] > 0, "divergent writes must COW"
    assert (shared_eng.stats["peak_pages_used"]
            < plain_eng.stats["peak_pages_used"]), \
        "sharing must reduce peak pool consumption"


def test_drop_prefix_refcounts_survive_live_sharers(model):
    """Dropping a prefix while sequences still share its pages must not
    free them out from under the sharers (refcount keeps them live)."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8)
    eng = ServeEngine(cfg, params, ecfg)
    rng = np.random.default_rng(9)
    prefix = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    key = eng.register_prefix(prefix)
    p = np.concatenate([prefix,
                        rng.integers(1, cfg.vocab_size, 2).astype(np.int32)])
    req = Request(rid=0, prompt=p, max_new_tokens=6)
    eng.submit(req)
    eng.step()                                   # admit + first decode
    assert eng.stats["prefix_hits"] == 1
    eng.drop_prefix(key)                         # prefix gone, sharer lives
    eng.run_until_drained(max_steps=200)
    assert req.generated == reference_generate(cfg, params, p, 6)
    assert eng.allocator.used_pages == 1         # everything drained


# ------------------------------------------------------------- tier pinning


def test_priority_tenant_prefix_pinned_fast_tier(model):
    """A pin_fast tenant's registered prefix is persisted into the prefix
    region and pinned into the fast tier via the §14.3 tier-hint path."""
    cfg, params = model
    PS = 4096
    slow = HostArrayStore(np.zeros(16 * PS, np.uint8))
    fast = HostArrayStore(np.zeros(4 * PS, np.uint8))
    store = TieredStore(fast=fast, slow=slow, extent_size=PS)
    region = umap(store, config=UMapConfig(page_size=PS,
                                           buffer_size=4 * PS,
                                           num_fillers=1, num_evictors=1))
    try:
        ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=64,
                            max_pages_per_seq=16, prefill_bucket=8)
        eng = ServeEngine(cfg, params, ecfg, prefix_region=region)
        eng.add_tenant(Tenant("gold", weight=2.0, priority=1, pin_fast=True))
        rng = np.random.default_rng(13)
        prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
        key = eng.register_prefix(prefix, tenant="gold")
        assert eng._prefixes[key].pinned
        region.flush()
        st = store.tier_stats()
        assert st["pinned_fast"] > 0, "pin_fast hint did not reach the tier"
        # the persisted bytes round-trip through the region
        got = np.frombuffer(region.read(0, prefix.nbytes), np.int32)
        np.testing.assert_array_equal(got, prefix)
    finally:
        uunmap(region)


# ------------------------------------------------------------ SLO admission


def test_slo_admission_orders_by_headroom(model):
    """With one free lane, the tight-but-feasible deadline is admitted ahead
    of earlier-submitted laxer requests; infeasible deadlines are deferred
    (counted) but still finish — nothing starves."""
    cfg, params = model
    # seed estimates of 1 s/step make a 2 s deadline infeasible for a
    # 2-token request (est = 1 + 2*1 = 3 s) while 30 s / 120 s are feasible
    ecfg = EngineConfig(max_batch=1, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8,
                        est_step_s=1.0, est_prefill_s=1.0, slo_safety=1.0)
    eng = ServeEngine(cfg, params, ecfg)
    rng = np.random.default_rng(17)
    mk = lambda rid, dl: Request(
        rid=rid, prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
        max_new_tokens=2, deadline_s=dl)
    lax = mk(0, 120.0)
    tight = mk(1, 30.0)
    infeasible = mk(2, 2.0)      # headroom > 0 but < estimated service time
    for r in (lax, tight, infeasible):
        eng.submit(r)
    eng.step()
    assert tight.rid in eng.lane_of or tight.done, \
        "tightest feasible deadline must win the single lane"
    assert eng.stats["slo_deferrals"] >= 1, \
        "infeasible headroom must be deferred in the strict pass"
    eng.run_until_drained(max_steps=500)
    assert_none_lost(eng, [lax, tight, infeasible])
    assert all(r.done or r.expired for r in (lax, tight, infeasible))


# ----------------------------------------------------- telemetry end-to-end


def test_serve_collector_parity_after_drained_run(model):
    """After a drained multi-tenant run, the scraped exposition's counter
    families equal the engine's stats dict — per-tenant labels included
    (the aggregate == sum(per_shard) parity pattern applied to serving)."""
    from test_telemetry import parse_exposition

    cfg, params = model
    ecfg = EngineConfig(max_batch=4, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8)
    eng = ServeEngine(cfg, params, ecfg)
    eng.add_tenant(Tenant("gold", weight=2.0, priority=1))
    eng.add_tenant(Tenant("bronze", weight=1.0))
    rng = np.random.default_rng(19)
    prefix = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    eng.register_prefix(prefix, tenant="gold")
    reqs = []
    for i in range(6):
        p = np.concatenate(
            [prefix, rng.integers(1, cfg.vocab_size, 2).astype(np.int32)]) \
            if i % 2 else rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
        reqs.append(Request(rid=i, prompt=p, max_new_tokens=3,
                            tenant="gold" if i % 2 else "bronze"))
        eng.submit(reqs[-1])
    reg = TelemetryRegistry()
    eng.register_telemetry(registry=reg, label="t")
    eng.run_until_drained(max_steps=500)
    assert_none_lost(eng, reqs)

    fams = parse_exposition(reg.render())
    flat = {k: v for k, v in eng.stats.items() if k != "per_tenant"}
    scraped_aggregate = {
        "steps": "umap_serve_steps_total",
        "prefills": "umap_serve_prefills_total",
        "evictions": "umap_serve_evictions_total",
        "requeues": "umap_serve_requeues_total",
        "admission_pauses": "umap_serve_admission_pauses_total",
        "slo_deferrals": "umap_serve_slo_deferrals_total",
        "slo_misses": "umap_serve_slo_misses_total",
        "expired": "umap_serve_expired_total",
        "victim_evictions": "umap_serve_victim_evictions_total",
        "cow_copies": "umap_serve_cow_copies_total",
        "shared_pages_mapped": "umap_serve_shared_pages_mapped_total",
        "prefix_hits": "umap_serve_prefix_hits_total",
        "prefix_drops": "umap_serve_prefix_drops_total",
        "peak_pages_used": "umap_serve_peak_pages_used",
    }
    for key, fam in scraped_aggregate.items():
        assert fams[fam]["samples"][0][2] == float(flat[key]), (key, fam)
    assert fams["umap_serve_finished_requests_total"]["samples"][0][2] \
        == len(eng.finished)
    # per-tenant labels: every tenant appears, values equal the stats dict
    for key, fam in (("prefills", "umap_serve_tenant_prefills_total"),
                     ("finished", "umap_serve_tenant_finished_total"),
                     ("tokens_generated",
                      "umap_serve_tenant_tokens_generated_total")):
        got = {lab["tenant"]: v for _, lab, v in fams[fam]["samples"]}
        want = {t: float(st[key])
                for t, st in eng.stats["per_tenant"].items()}
        assert got == want, (key, fam)


# ------------------------------------------ degraded-mode admission (§17.9)


class _DegradedPagingSvc:
    """Duck-typed paging service: only what paging_degraded() probes."""

    def __init__(self):
        self.open = 0

    def open_breakers(self):
        return self.open


def _mk_deadline_req(cfg, rng, rid, deadline_s):
    return Request(rid=rid,
                   prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
                   max_new_tokens=2, deadline_s=deadline_s)


def test_degraded_paging_sheds_infeasible_deadlines(model):
    """While the paging service reports an open breaker, service-time
    estimates carry degrade_multiplier: a deadline that is feasible when
    healthy (est 3 s < 10 s) becomes infeasible degraded (est 30 s) and is
    shed at admission — retired terminally via shed_requests, never
    counted as restart exhaustion."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8,
                        est_step_s=1.0, est_prefill_s=1.0, slo_safety=1.0,
                        degrade_multiplier=10.0)
    svc = _DegradedPagingSvc()
    eng = ServeEngine(cfg, params, ecfg, paging_service=svc)
    rng = np.random.default_rng(23)
    req = _mk_deadline_req(cfg, rng, 0, deadline_s=10.0)
    svc.open = 1
    assert eng.paging_degraded() is True
    eng.submit(req)
    eng.step()
    assert eng.stats["shed_requests"] == 1
    assert eng.stats["per_tenant"]["default"]["shed_requests"] == 1
    assert req.expired and req.slo_miss and req in eng.finished
    assert eng.stats["expired"] == 0, "shed is not restart exhaustion"
    assert_none_lost(eng, [req])


def test_healthy_paging_admits_same_deadline(model):
    """The identical request sails through admission when no breaker is
    open — the degraded multiplier must not leak into the healthy path."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8,
                        est_step_s=1.0, est_prefill_s=1.0, slo_safety=1.0,
                        degrade_multiplier=10.0)
    svc = _DegradedPagingSvc()
    eng = ServeEngine(cfg, params, ecfg, paging_service=svc)
    rng = np.random.default_rng(23)
    req = _mk_deadline_req(cfg, rng, 0, deadline_s=10.0)
    eng.submit(req)
    eng.run_until_drained(max_steps=200)
    assert eng.stats["shed_requests"] == 0
    assert req.done and not req.expired
    assert_none_lost(eng, [req])


def test_degrade_shed_opt_out_keeps_request(model):
    """degrade_shed=False: the degraded estimate may defer the request but
    never sheds it — it still retires through the normal lifecycle."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=2, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8,
                        est_step_s=1.0, est_prefill_s=1.0, slo_safety=1.0,
                        degrade_multiplier=10.0, degrade_shed=False)
    svc = _DegradedPagingSvc()
    eng = ServeEngine(cfg, params, ecfg, paging_service=svc)
    svc.open = 1
    rng = np.random.default_rng(23)
    req = _mk_deadline_req(cfg, rng, 0, deadline_s=10.0)
    eng.submit(req)
    eng.run_until_drained(max_steps=200)
    assert eng.stats["shed_requests"] == 0
    assert req.done or req.expired       # retired, never silently dropped
    assert_none_lost(eng, [req])


def test_paging_degraded_probe_is_defensive(model):
    """A paging service whose health probe raises must read as healthy —
    the degradation probe can never take the engine down."""
    cfg, params = model
    ecfg = EngineConfig(max_batch=1, page_size=4, num_pages=64,
                        max_pages_per_seq=16, prefill_bucket=8)

    class _Broken:
        def open_breakers(self):
            raise RuntimeError("probe exploded")

    eng = ServeEngine(cfg, params, ecfg, paging_service=_Broken())
    assert eng.paging_degraded() is False
    eng2 = ServeEngine(cfg, params, ecfg)          # no service wired at all
    assert eng2.paging_degraded() is False


# ------------------------------------------------ program spans and names


def test_engine_programs_are_named(model):
    """The trace names the engine's programs ``jit_decode_step`` and
    ``jit_prefill``, which the benchmark's readers find them by."""
    cfg, params = model
    eng = ServeEngine(cfg, params, EngineConfig(
        max_batch=2, page_size=4, num_pages=16, max_pages_per_seq=8,
        prefill_bucket=8))
    lanes = jnp.zeros(2, jnp.int32)
    text = eng._decode.lower(params, eng._caches, lanes, lanes).as_text()
    assert "module @jit_decode_step " in text
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    text = eng._prefill.lower(params, batch, M.init_cache(cfg, 1, 16)).as_text()
    assert "module @jit_prefill " in text


def test_traced_admissions_count_held_prefix_tokens(model, tmp_path):
    """Under a profiler capture each engine step records its admission,
    page work, table sync and decode; each prefill records, under its
    request id, the positions it computed and those a shared prefix's
    pages already held, which the engine's counters and collector hold
    too."""
    from repro.telemetry import spans
    from repro.telemetry.collectors import ServeCollector

    cfg, params = model
    eng = ServeEngine(cfg, params, EngineConfig(
        max_batch=4, page_size=4, num_pages=64, max_pages_per_seq=16,
        prefill_bucket=8))
    rng = np.random.default_rng(23)
    prefix = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    eng.register_prefix(prefix)
    prompts = [np.concatenate([prefix, rng.integers(1, cfg.vocab_size, 3)
                               .astype(np.int32)]),
               rng.integers(1, cfg.vocab_size, 6).astype(np.int32)]
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=3))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run_until_drained(max_steps=100)
    finally:
        jax.profiler.stop_trace()
    recs = spans.session()
    by_id = {r.span_id: r for r in recs}
    assert sum(r.name == "umap.engine.decode" for r in recs) \
        == eng.stats["steps"] > 0
    for name in ("umap.engine.admit", "umap.engine.page_work",
                 "umap.engine.sync_tables", "umap.engine.decode"):
        assert {by_id[r.parent_id].name for r in recs if r.name == name} \
            == {"umap.engine.step"}
    pre = {r.rid: r for r in recs if r.name == "umap.engine.prefill"}
    assert {by_id[r.parent_id].name for r in pre.values()} \
        == {"umap.engine.admit"}
    assert {r.parent_id for r in recs if r.name == "umap.engine.install"} \
        == {r.span_id for r in pre.values()}
    meta = cfg.num_meta_tokens
    assert {rid: r.counts for rid, r in pre.items()} == {
        0: {"tokens": len(prompts[0]) - 1 + meta,
            "held_tokens": len(prefix) + meta},
        1: {"tokens": len(prompts[1]) - 1 + meta, "held_tokens": 0}}
    assert eng.stats["prefill_tokens"] == sum(
        r.counts["tokens"] for r in pre.values())
    assert eng.stats["prefill_tokens_held"] == len(prefix) + meta
    fams = {f.name: f for f in ServeCollector(engine=eng).collect()}
    assert fams["umap_serve_prefill_tokens_total"].samples[0][2] \
        == eng.stats["prefill_tokens"]
    assert fams["umap_serve_prefill_tokens_held_total"].samples[0][2] \
        == len(prefix) + meta
