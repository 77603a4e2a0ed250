"""Adaptive engine + batched fault coalescing (DESIGN.md §8–9).

Covers: classifier phase detection (sequential -> random transition, stride
detection, hysteresis damping), batched-fill correctness (coalesced runs
install every page, blocked readers wake, stats count, fewer store calls),
static-hint precedence, runtime policy swap, and an mmap_compat regression
proving ``adaptive=False`` preserves the seed behavior.
"""

import random
import threading

import numpy as np
import pytest

from repro.core import (
    AccessAdvice,
    AccessPatternClassifier,
    HostArrayStore,
    Phase,
    RemoteStore,
    UMapConfig,
    advice_for_phase,
    phase_for_advice,
    umap,
    uunmap,
)


# --------------------------------------------------------------- classifier


def make_clf(**kw):
    kw.setdefault("window", 16)
    kw.setdefault("min_samples", 8)
    kw.setdefault("interval", 4)
    kw.setdefault("hysteresis", 2)
    return AccessPatternClassifier(**kw)


def feed(clf, pages):
    last = None
    for p in pages:
        d = clf.observe(p)
        if d is not None:
            last = d
    return last


def test_sequential_detection():
    clf = make_clf()
    d = feed(clf, range(64))
    assert d is not None and d.phase is Phase.SEQUENTIAL
    assert d.stride == 1 and d.read_ahead > 0


def test_sequential_to_random_transition():
    clf = make_clf()
    feed(clf, range(64))
    assert clf.phase is Phase.SEQUENTIAL
    rng = random.Random(7)
    d = feed(clf, [rng.randrange(100_000) for _ in range(200)])
    assert clf.phase is Phase.RANDOM
    assert d is not None and d.read_ahead == 0
    assert clf.transitions >= 1


def test_stride_detection():
    clf = make_clf()
    d = feed(clf, range(0, 64 * 7, 7))
    assert d is not None and d.phase is Phase.STRIDED and d.stride == 7


def test_hysteresis_damps_noise():
    """A few stray faults inside a sequential scan must not flip the phase."""
    clf = make_clf(window=16, min_samples=8, interval=4, hysteresis=3)
    feed(clf, range(64))
    assert clf.phase is Phase.SEQUENTIAL
    # one noisy burst shorter than hysteresis*interval, then sequential again
    feed(clf, [9000, 17, 4400])
    feed(clf, range(64, 128))
    assert clf.phase is Phase.SEQUENTIAL
    assert clf.transitions == 0


def test_scan_with_reuse_detection():
    """A cyclic scan (revisit after wraparound) classifies as SCAN_REUSE."""
    clf = make_clf(window=16, min_samples=8, interval=4, hysteresis=1)
    for _ in range(4):                      # loop over the same 24 pages
        feed(clf, range(24))
    assert clf.phase is Phase.SCAN_REUSE
    from repro.core import PHASE_SETTINGS
    assert PHASE_SETTINGS[Phase.SCAN_REUSE]["eviction_policy"] == "swa"


def test_phase_advice_bridge_round_trip():
    assert advice_for_phase(Phase.SEQUENTIAL) is AccessAdvice.SEQUENTIAL
    assert advice_for_phase(Phase.SCAN_REUSE) is AccessAdvice.STREAMING
    assert phase_for_advice(AccessAdvice.STRIDED) is Phase.STRIDED
    for ph in Phase:
        assert phase_for_advice(advice_for_phase(ph)) in Phase


# ------------------------------------------------------------ batched fills


def make_region(nbytes=256 * 4096, page_size=4096, slots=None, **cfg_kw):
    data = (np.arange(nbytes) % 251).astype(np.uint8)
    store = HostArrayStore(data.copy())
    slots = slots if slots is not None else nbytes // page_size
    cfg = UMapConfig(page_size=page_size, buffer_size=slots * page_size,
                     num_fillers=4, num_evictors=2, **cfg_kw)
    return umap(store, config=cfg), data, store


def test_coalesced_fill_correct_and_counted():
    r, data, store = make_region(max_batch_pages=16)
    try:
        out = r.read(0, 128 * 4096)         # posts 128 adjacent fills up front
        assert np.array_equal(out, data[: 128 * 4096])
        st = r.stats()
        assert st["coalesced_fills"] >= 1, "no fills were coalesced"
        assert st["coalesced_pages"] > st["coalesced_fills"]
        # vectorized store: far fewer read calls than pages moved
        assert store.num_reads < 128
    finally:
        uunmap(r)


def test_coalescing_disabled_matches_page_count():
    r, data, store = make_region(max_batch_pages=1)
    try:
        out = r.read(0, 128 * 4096)
        assert np.array_equal(out, data[: 128 * 4096])
        st = r.stats()
        assert st["coalesced_fills"] == 0
        assert store.num_reads >= 128       # one store call per page
    finally:
        uunmap(r)


def test_coalesced_fill_wakes_all_blocked_readers():
    """Threads blocked on different pages of one run all wake on install."""
    nbytes = 64 * 4096
    inner = HostArrayStore((np.arange(nbytes) % 251).astype(np.uint8))
    store = RemoteStore(inner, latency_s=5e-3, bandwidth_Bps=1e9)
    cfg = UMapConfig(page_size=4096, buffer_size=64 * 4096, num_fillers=2,
                     num_evictors=1, max_batch_pages=32)
    r = umap(store, config=cfg)
    results, errors = {}, []

    def reader(pno):
        try:
            got = r.read(pno * 4096, 4096)
            results[pno] = got[0]
        except Exception as e:  # pragma: no cover - diagnostic
            errors.append((pno, e))

    try:
        r.service.request_fills(r, list(range(32)))   # one adjacent run
        ts = [threading.Thread(target=reader, args=(p,)) for p in range(32)]
        [t.start() for t in ts]
        [t.join(timeout=10) for t in ts]
        assert not errors
        assert len(results) == 32, "a blocked reader never woke"
        st = r.stats()
        assert st["coalesced_fills"] >= 1
        # the run paid ~1 latency charge, not 32 (store calls, not pages)
        assert store.num_reads < 32
    finally:
        uunmap(r)


def test_batch_respects_store_hint():
    """Effective batch = min(config.max_batch_pages, store.batch_read_hint)."""
    r, data, store = make_region(max_batch_pages=64)
    try:
        store.batch_read_hint = 4
        r.read(0, 64 * 4096)
        st = r.stats()
        if st["coalesced_fills"]:
            assert st["coalesced_pages"] / st["coalesced_fills"] <= 4
    finally:
        uunmap(r)


# --------------------------------------------------------- adaptive regions


def test_adaptive_sequential_scan_cuts_demand_faults():
    r, data, _ = make_region(adaptive=True, pattern_min_samples=8,
                             pattern_interval=4, pattern_hysteresis=2)
    try:
        for pno in range(256):
            assert np.array_equal(r.read(pno * 4096, 4096),
                                  data[pno * 4096 : (pno + 1) * 4096])
        st = r.stats()
        assert st["pattern_transitions"] >= 1, "classifier never retuned"
        assert r.readahead_pages > 0, "readahead was not raised"
        assert st["demand_faults"] < 256, "adaptation saved no faults"
        snap = r.service.pattern_snapshot(r.region_id)
        assert snap["phase"] == "sequential"
    finally:
        uunmap(r)


def test_adaptive_backward_strided_scan_prefetches_downward():
    """Negative detected stride must read ahead *downward* (review fix)."""
    n = 512 * 4096
    data = (np.arange(n) % 251).astype(np.uint8)
    cfg = UMapConfig(page_size=4096, buffer_size=512 * 4096, num_fillers=4,
                     num_evictors=2, adaptive=True, pattern_min_samples=8,
                     pattern_interval=4, pattern_hysteresis=2)
    r = umap(HostArrayStore(data.copy()), config=cfg)
    try:
        for pno in range(511, 200, -2):
            assert np.array_equal(r.read(pno * 4096, 4096),
                                  data[pno * 4096 : (pno + 1) * 4096])
        snap = r.service.pattern_snapshot(r.region_id)
        assert snap["phase"] == "strided" and snap["stride"] == -2
        assert r.stats()["prefetch_hits"] > 0, "no downward readahead hits"
    finally:
        uunmap(r)


def test_static_hint_pins_region_against_classifier():
    """Explicit readahead_pages => classifier must never retune (§3.6 bridge)."""
    data = (np.arange(256 * 4096) % 251).astype(np.uint8)
    store = HostArrayStore(data.copy())
    cfg = UMapConfig(page_size=4096, buffer_size=256 * 4096, num_fillers=4,
                     num_evictors=2, adaptive=True, pattern_min_samples=8,
                     pattern_interval=4, pattern_hysteresis=2)
    r = umap(store, config=cfg, readahead_pages=3)
    try:
        assert r.hint_pinned
        for pno in range(256):
            r.read(pno * 4096, 4096)
        assert r.readahead_pages == 3, "classifier overrode a pinned hint"
        assert r.stats()["pattern_transitions"] == 0
    finally:
        uunmap(r)


def test_advise_pins_and_applies_settings():
    r, data, _ = make_region(adaptive=True)
    try:
        r.advise(AccessAdvice.STREAMING)
        assert r.hint_pinned
        assert r.readahead_pages == 16
        assert r.service.policy.name == "swa"
        for pno in range(128):
            r.read(pno * 4096, 4096)
        assert r.readahead_pages == 16      # still pinned
    finally:
        uunmap(r)


def test_runtime_policy_swap_preserves_residency():
    r, data, _ = make_region(nbytes=64 * 4096, slots=16)
    try:
        for pno in range(32):
            r.read(pno * 4096, 4096)
        resident_before = r.service.resident_pages()
        r.service.set_eviction_policy("swa")
        assert r.service.policy.name == "swa"
        assert r.service.resident_pages() == resident_before
        # eviction still functions under the swapped policy
        for pno in range(32, 64):
            assert np.array_equal(r.read(pno * 4096, 4096),
                                  data[pno * 4096 : (pno + 1) * 4096])
        assert r.service.buffer.used_slots <= 16
    finally:
        uunmap(r)


def test_policy_swap_keeps_pages_mid_writeback_evictable():
    """Regression: a page posted for write-back (CLEANING) when the policy
    is swapped returns to PRESENT once cleaned.  The new policy must track
    it, or its slot can never be reclaimed: the OOC trainer's adaptive
    moments region then livelocked in ``lease_run`` on a stripe short of
    one slot."""
    from repro.core import PageState

    r, data, _ = make_region(nbytes=16 * 4096, slots=4)
    svc = r.service
    try:
        r.write(0, np.full(4096, 7, np.uint8))          # page 0 dirty
        key = (r.region_id, 0)
        shard = svc._shard_of(key)
        with shard.lock:
            e = shard.table.get(key)
            e.state = PageState.CLEANING                # as a poster does
        svc.set_eviction_policy("swa")
        with shard.lock:                                # as a cleaner does
            e.state = PageState.PRESENT
            shard.table.mark_clean(e)
        assert key in shard.policy.pick_victims(svc.buffer.num_slots,
                                                lambda k: True)
        for pno in range(1, 16):                        # churn past page 0
            assert np.array_equal(r.read(pno * 4096, 4096),
                                  data[pno * 4096:(pno + 1) * 4096])
        assert svc.table.get(key) is None               # page 0 was evicted
    finally:
        uunmap(r)


# ----------------------------------------------------- regression (baseline)


def test_mmap_compat_unaffected_by_new_engine():
    """adaptive=False + mmap_compat: byte-identical semantics to the seed —
    synchronous resolution, heuristic readahead, no coalescing, no retunes."""
    nbytes = 128 * 4096
    data = (np.arange(nbytes) % 251).astype(np.uint8)
    cfg = UMapConfig.mmap_baseline(buffer_size=64 * 4096)
    assert cfg.adaptive is False and cfg.max_batch_pages == 1
    r = umap(HostArrayStore(data.copy()), config=cfg)
    try:
        assert len(r.service._fillers) == 0
        for pno in range(64):
            assert np.array_equal(r.read(pno * 4096, 4096),
                                  data[pno * 4096 : (pno + 1) * 4096])
        st = r.stats()
        assert st["coalesced_fills"] == 0
        assert st["pattern_transitions"] == 0
        assert st["prefetch_fills"] > 0          # heuristic readahead intact
        assert st["demand_faults"] < 64
    finally:
        uunmap(r)


def test_default_config_has_adaptive_off():
    cfg = UMapConfig()
    assert cfg.adaptive is False
    r, data, _ = make_region()               # defaults: no classifier attached
    try:
        r.read(0, 4096)
        assert r.service.pattern_snapshot(r.region_id) is None
        assert r.stats()["pattern_transitions"] == 0
    finally:
        uunmap(r)
