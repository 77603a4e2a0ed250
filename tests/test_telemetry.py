"""Telemetry substrate (DESIGN.md §15): one test class per collector
(omnistat-style per-collector harness), plus the metric primitives, the
registry, the HTTP exporter end-to-end, the scrape-path lock rules
(a scrape completes while every shard lock is held by someone else), a
fault-storm-while-scraping run asserting scrapes neither block fills nor
perturb snapshot parity, and the ``UMAP_TELEMETRY_PORT`` autostart.
"""

import re
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import telemetry
from repro.core import HostArrayStore, UMapConfig, umap, uunmap
from repro.core.store import TieredStore
from repro.telemetry import (
    CONTENT_TYPE,
    TelemetryExporter,
    TelemetryRegistry,
)
from repro.telemetry.collectors import (
    LeaseCollector,
    PagerCollector,
    ProcessCollector,
    ResilienceCollector,
    ServeCollector,
    TieringCollector,
)
from repro.telemetry.metrics import (
    DEFAULT_BUCKETS,
    HistogramState,
    MetricFamily,
    escape_label_value,
    format_value,
    validate_label_name,
    validate_metric_name,
)

PS = 4096

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})? (?P<value>\S+)$")


def parse_exposition(text: str):
    """Prometheus text -> {family: {"type": ..., "samples":
    [(series_name, {label: value}, float)]}}; also validates that every
    sample line is preceded by its family's HELP/TYPE header."""
    families, current = {}, None
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            current = line.split(" ", 3)[2]
            families.setdefault(current, {"type": None, "samples": []})
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name == current, f"TYPE {name} without its HELP"
            families[name]["type"] = kind
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            sname = m.group("name")
            assert current and sname.startswith(current), \
                f"sample {sname} outside its family block ({current})"
            labels = {}
            if m.group("labels"):
                for part in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                       m.group("labels")):
                    labels[part[0]] = part[1]
            families[current]["samples"].append(
                (sname, labels, float(m.group("value"))))
    return families


def families_of(collector):
    out = {}
    for fam in collector.collect():
        assert fam.name not in out, f"duplicate family {fam.name}"
        out[fam.name] = fam
    return out


def make_region(npages=64, shards=4, tiered=False, **cfg_kw):
    data = (np.arange(npages * PS) % 251).astype(np.uint8)
    store = HostArrayStore(data)
    if tiered:
        fast = HostArrayStore(np.zeros(npages * PS // 4, np.uint8))
        store = TieredStore(fast=fast, slow=store, extent_size=4 * PS)
    cfg = UMapConfig(page_size=PS, buffer_size=npages * PS, num_fillers=2,
                     num_evictors=1, shards=shards, **cfg_kw)
    return umap(store, config=cfg)


# --------------------------------------------------------------- primitives


class TestMetricPrimitives:
    def test_metric_and_label_name_validation(self):
        validate_metric_name("umap_pager_demand_faults_total")
        for bad in ("0abc", "has space", "dash-ed", ""):
            with pytest.raises(ValueError):
                validate_metric_name(bad)
        validate_label_name("shard")
        for bad in ("__reserved", "0x", "a-b"):
            with pytest.raises(ValueError):
                validate_label_name(bad)

    def test_label_value_escaping(self):
        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'

    def test_format_value(self):
        assert format_value(3) == "3"
        assert format_value(2.0) == "2"
        assert format_value(0.25) == "0.25"
        assert format_value(float("inf")) == "+Inf"
        assert format_value(float("-inf")) == "-Inf"
        assert format_value(float("nan")) == "NaN"

    def test_family_render_golden(self):
        fam = MetricFamily("umap_x_total", "counter", "Help text",
                           {"source": "s0"})
        fam.add(7, shard=3)
        assert fam.render() == (
            "# HELP umap_x_total Help text\n"
            "# TYPE umap_x_total counter\n"
            'umap_x_total{shard="3",source="s0"} 7\n')

    def test_family_rejects_bad_kind(self):
        with pytest.raises(ValueError):
            MetricFamily("umap_x", "summary", "h")

    def test_histogram_buckets_are_cumulative(self):
        h = HistogramState(buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        fam = h.to_family("umap_d_seconds", "h")
        by_le = {lab["le"]: val for sfx, lab, val in
                 ((s, la, v) for s, la, v in fam.samples) if sfx == "_bucket"}
        assert by_le == {"0.01": 1, "0.1": 2, "1": 3, "+Inf": 4}
        sums = {sfx: v for sfx, _, v in fam.samples if sfx in ("_sum", "_count")}
        assert sums["_count"] == 4 and sums["_sum"] == pytest.approx(5.555)

    def test_default_buckets_sorted(self):
        assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)


# ----------------------------------------------------------------- registry


class TestRegistry:
    def test_register_dedupes_names_and_unregisters(self):
        reg = TelemetryRegistry()
        a = reg.register(ProcessCollector(label="x"))
        b = reg.register(ProcessCollector(label="x"))
        assert a == "process:x" and b == "process:x#2"
        assert set(reg.collector_names()) == {a, b}
        assert reg.unregister(b) and not reg.unregister(b)
        assert reg.collector_names() == [a]

    def test_merges_same_family_from_two_collectors(self):
        class One(ProcessCollector):
            kind = "one"

            def collect(self):
                return [self.c1("umap_thing_total", "h", 1)]

        reg = TelemetryRegistry()
        reg.register(One(label="a"))
        reg.register(One(label="b"))
        fams = parse_exposition(reg.render())
        srcs = {lab["source"] for _, lab, _ in
                fams["umap_thing_total"]["samples"]}
        assert srcs == {"a", "b"}
        # merged into ONE family block: exactly one TYPE line
        assert reg.render().count("# TYPE umap_thing_total counter") == 1

    def test_collector_failure_is_counted_not_fatal(self):
        class Broken:
            name = "broken:x"

            def collect(self):
                raise RuntimeError("boom")

        reg = TelemetryRegistry()
        reg.register(Broken())
        reg.register(ProcessCollector(label="ok"))
        fams = parse_exposition(reg.render())
        assert "umap_process_threads" in fams          # scrape survived
        errs = {lab["collector"]: v for _, lab, v in
                fams["umap_telemetry_collect_errors_total"]["samples"]}
        assert errs["broken:x"] == 1

    def test_self_telemetry_scrapes_and_duration(self):
        reg = TelemetryRegistry()
        first = parse_exposition(reg.render())
        second = parse_exposition(reg.render())
        n1 = first["umap_telemetry_scrapes_total"]["samples"][0][2]
        n2 = second["umap_telemetry_scrapes_total"]["samples"][0][2]
        assert (n1, n2) == (1, 2)
        hist = second["umap_telemetry_scrape_duration_seconds"]
        assert hist["type"] == "histogram"
        inf = [v for s, lab, v in hist["samples"]
               if lab.get("le") == "+Inf"]
        assert inf == [1]                              # first render observed

    def test_type_conflict_keeps_first_and_counts(self):
        class C1(ProcessCollector):
            def collect(self):
                return [self.c1("umap_conflict", "h", 1)]

        class C2(ProcessCollector):
            def collect(self):
                return [self.g1("umap_conflict", "h", 2)]

        reg = TelemetryRegistry()
        reg.register(C1(label="a"), name="a")
        reg.register(C2(label="b"), name="b")
        fams = parse_exposition(reg.render())
        assert fams["umap_conflict"]["type"] == "counter"
        errs = {lab["collector"] for _, lab, _ in
                fams["umap_telemetry_collect_errors_total"]["samples"]}
        assert "type-conflict:umap_conflict" in errs


# ----------------------------------------------------------- PagerCollector


PAGER_COUNTERS = {
    "umap_pager_demand_faults_total", "umap_pager_page_hits_total",
    "umap_pager_wait_hits_total", "umap_pager_prefetch_fills_total",
    "umap_pager_prefetch_hits_total", "umap_pager_evictions_total",
    "umap_pager_writebacks_total", "umap_pager_watermark_flushes_total",
    "umap_pager_coalesced_fills_total", "umap_pager_coalesced_pages_total",
    "umap_pager_coalesced_writebacks_total",
    "umap_pager_writeback_pages_total", "umap_pager_fill_stalls_total",
    "umap_pager_lock_contended_total", "umap_pager_steals_total",
    "umap_pager_stolen_work_total", "umap_pager_io_errors_total",
    "umap_pager_writeback_errors_total",
    "umap_pager_quarantine_retries_total",
    "umap_pager_pattern_transitions_total",
    "umap_pager_tier_promotions_total", "umap_pager_tier_demotions_total",
    "umap_pager_tier_errors_total", "umap_pager_tier_cycles_total",
    "umap_pager_shard_demand_faults_total",
    "umap_pager_shard_lock_contended_total",
    "umap_pager_shard_fill_stalls_total",
    "umap_pager_filler_fills_total",
}
PAGER_GAUGES = {
    "umap_pager_shards", "umap_pager_fill_queue_peak",
    "umap_pager_dirty_ratio", "umap_pager_buffer_slots",
    "umap_pager_page_size_bytes",
    # quarantine population can shrink again on §17.4 re-post: gauges
    "umap_pager_quarantined_pages", "umap_pager_shard_quarantined_pages",
}


class TestPagerCollector:
    def test_exact_family_names_and_types(self):
        r = make_region(shards=4)
        try:
            for pno in range(8):
                r.read(pno * PS, 64)
            fams = families_of(PagerCollector(r.service, label="s"))
            assert set(fams) == PAGER_COUNTERS | PAGER_GAUGES
            for name in PAGER_COUNTERS:
                assert fams[name].kind == "counter", name
            for name in PAGER_GAUGES:
                assert fams[name].kind == "gauge", name
        finally:
            uunmap(r)

    def test_label_sets_shard_filler_source(self):
        r = make_region(shards=4)
        try:
            for pno in range(16):
                r.read(pno * PS, 64)
            fams = families_of(PagerCollector(r.service, label="svcX"))
            for fam in fams.values():
                for _, labels, _ in fam.samples:
                    assert labels["source"] == "svcX", fam.name
            shard_labels = {lab["shard"] for _, lab, _ in
                            fams["umap_pager_shard_demand_faults_total"].samples}
            assert shard_labels == {"0", "1", "2", "3"}
            sum_per_shard = sum(v for _, _, v in
                                fams["umap_pager_shard_demand_faults_total"].samples)
            agg = fams["umap_pager_demand_faults_total"].samples[0][2]
            assert agg == sum_per_shard == 16
            fill_sum = sum(v for _, _, v in
                           fams["umap_pager_filler_fills_total"].samples)
            assert fill_sum == 16
            assert fams["umap_pager_shards"].samples[0][2] == 4
            assert fams["umap_pager_page_size_bytes"].samples[0][2] == PS
        finally:
            uunmap(r)

    def test_counters_monotonic_across_scrapes(self):
        r = make_region(shards=2)
        try:
            col = PagerCollector(r.service, label="s")
            for pno in range(4):
                r.read(pno * PS, 64)
            first = {f.name: sum(v for *_, v in f.samples)
                     for f in col.collect() if f.kind == "counter"}
            for pno in range(4, 12):
                r.read(pno * PS, 64)
            r.write(0, np.full(32, 7, np.uint8))
            r.flush()
            second = {f.name: sum(v for *_, v in f.samples)
                      for f in col.collect() if f.kind == "counter"}
            assert set(first) == set(second)
            for name, v1 in first.items():
                assert second[name] >= v1, f"{name} went backwards"
            assert second["umap_pager_demand_faults_total"] == 12
            assert second["umap_pager_writebacks_total"] >= 1
        finally:
            uunmap(r)


# --------------------------------------------------------- TieringCollector


TIER_COUNTERS = {
    "umap_tier_promotions_total", "umap_tier_demotions_total",
    "umap_tier_migration_aborts_total", "umap_tier_read_bytes_total",
    "umap_tier_migration_write_bytes_total",
    "umap_tier_shadow_demotions_total", "umap_tier_failovers_total",
}
TIER_GAUGES = {
    "umap_tier_resident_extents", "umap_tier_free_slots",
    "umap_tier_slots", "umap_tier_utility", "umap_tier_latency_seconds",
    "umap_tier_dirty_extents", "umap_tier_pinned_fast_extents",
    "umap_tier_levels", "umap_tier_extent_size_bytes",
}
# families carrying one sample per chain level, labeled tier="0"..tier="N"
TIER_PER_LEVEL = {
    "umap_tier_resident_extents", "umap_tier_free_slots", "umap_tier_slots",
    "umap_tier_utility", "umap_tier_latency_seconds",
    "umap_tier_read_bytes_total", "umap_tier_promotions_total",
    "umap_tier_demotions_total", "umap_tier_migration_write_bytes_total",
}


class TestTieringCollector:
    def _store(self, npages=32):
        slow = HostArrayStore((np.arange(npages * PS) % 251).astype(np.uint8))
        fast = HostArrayStore(np.zeros(npages * PS // 4, np.uint8))
        return TieredStore(fast=fast, slow=slow, extent_size=4 * PS)

    def test_exact_family_names_and_types(self):
        fams = families_of(TieringCollector(self._store(), label="t"))
        assert set(fams) == TIER_COUNTERS | TIER_GAUGES
        for name in TIER_COUNTERS:
            assert fams[name].kind == "counter", name
        for name in TIER_GAUGES:
            assert fams[name].kind == "gauge", name
        for fam in fams.values():
            for _, lab, _ in fam.samples:
                assert lab["source"] == "t", fam.name
                if fam.name in TIER_PER_LEVEL:
                    assert lab["tier"] in {"0", "1"}, fam.name
                else:
                    assert "tier" not in lab, fam.name

    def test_per_level_tier_labels(self):
        """One family per metric, one sample per chain level — a two-tier
        store emits tier=0 (fast) and tier=1 (base) under the SAME family
        names a deeper chain uses."""
        fams = families_of(TieringCollector(self._store(), label="t"))
        for name in TIER_PER_LEVEL - {"umap_tier_latency_seconds"}:
            tiers = [lab["tier"] for _, lab, _ in fams[name].samples]
            assert tiers == ["0", "1"], name
        lat = {(lab["tier"], lab["op"]) for _, lab, _ in
               fams["umap_tier_latency_seconds"].samples}
        assert lat == {("0", "read"), ("0", "write"),
                       ("1", "read"), ("1", "write")}
        assert fams["umap_tier_levels"].samples[0][2] == 2

    def test_tracks_promotions_and_residency(self):
        store = self._store()
        col = TieringCollector(store, label="t")
        before = families_of(col)
        assert before["umap_tier_resident_extents"].samples[0][2] == 0
        buf = np.empty(PS, np.uint8)
        store.read_into(0, buf)                     # promote_on_read extent 0
        after = families_of(col)

        def tier0(fam):
            return [v for _, lab, v in fam.samples if lab["tier"] == "0"][0]

        def base(fam):
            return [v for _, lab, v in fam.samples if lab["tier"] == "1"][0]

        assert tier0(after["umap_tier_promotions_total"]) >= 1
        assert tier0(after["umap_tier_resident_extents"]) >= 1
        assert base(after["umap_tier_read_bytes_total"]) >= PS
        # staging the promote copy wrote one extent into the fast tier
        assert tier0(after["umap_tier_migration_write_bytes_total"]) \
            >= store.extent_size
        # the staging read sampled the base tier's latency EWMA
        lat = {(lab["tier"], lab["op"]): v for _, lab, v in
               after["umap_tier_latency_seconds"].samples}
        assert lat[("1", "read")] > 0.0

    def test_relaxed_tier_stats_matches_locked_when_quiescent(self):
        store = self._store()
        buf = np.empty(PS, np.uint8)
        store.read_into(4 * PS, buf)
        assert store.tier_stats(relaxed=True) == store.tier_stats()

    def test_store_register_telemetry_roundtrip(self):
        reg = TelemetryRegistry()
        store = self._store()
        name = store.register_telemetry(registry=reg, label="direct")
        assert name == "tiering:direct"
        assert "umap_tier_slots" in parse_exposition(reg.render())


# ----------------------------------------------------------- LeaseCollector


class _FakeKV:
    def __init__(self):
        self.n = 0

    def stats(self):
        return {"leases": 3 + self.n, "lease_blocked_evictions": 1,
                "leased_sequences": 2, "pages_used": 5, "pages_free": 3,
                "occupancy": 0.625, "page_bytes": 1 << 14, "sequences": 4,
                "cow_copies": 2, "shared_pages": 1, "shared_pages_mapped": 3,
                "auto_evicted_pages": 6, "host_lock_contended": 0,
                "phases": {1: "stream", 2: "stream", 3: "random"}}


class TestLeaseCollector:
    def test_service_lease_metrics(self):
        r = make_region()
        try:
            with r.lease(2):
                pass
            fams = families_of(LeaseCollector(service=r.service, label="L"))
            assert set(fams) == {"umap_leases_granted_total",
                                 "umap_leases_blocked_evictions_total"}
            assert fams["umap_leases_granted_total"].kind == "counter"
            assert fams["umap_leases_granted_total"].samples[0][2] == 1
        finally:
            uunmap(r)

    def test_kv_and_weight_source_metrics(self):
        class _FakeWeightSource:
            staging_copies = 17

        fams = families_of(LeaseCollector(
            kv=_FakeKV(), weight_source=_FakeWeightSource(), label="L"))
        assert set(fams) == {"umap_kv_leases_granted_total",
                             "umap_kv_lease_blocked_evictions_total",
                             "umap_kv_leased_sequences",
                             "umap_weight_staging_copies_total"}
        assert fams["umap_kv_leased_sequences"].kind == "gauge"
        assert fams["umap_kv_leases_granted_total"].samples[0][2] == 3
        assert fams["umap_weight_staging_copies_total"].samples[0][2] == 17

    def test_kv_counter_monotonic(self):
        kv = _FakeKV()
        col = LeaseCollector(kv=kv, label="L")
        v1 = families_of(col)["umap_kv_leases_granted_total"].samples[0][2]
        kv.n += 5
        v2 = families_of(col)["umap_kv_leases_granted_total"].samples[0][2]
        assert v2 == v1 + 5

    def test_empty_collector_yields_nothing(self):
        assert families_of(LeaseCollector(label="L")) == {}


# ----------------------------------------------------------- ServeCollector


class _FakeAllocator:
    def occupancy(self):
        return 0.5


class _FakeEngine:
    def __init__(self):
        self.stats = {"steps": 10, "prefills": 4, "evictions": 1,
                      "requeues": 1, "admission_pauses": 2,
                      "slo_deferrals": 3, "slo_misses": 1, "expired": 0,
                      "victim_evictions": 2, "cow_copies": 5,
                      "shared_pages_mapped": 9, "prefix_hits": 6,
                      "prefix_drops": 1, "peak_pages_used": 7,
                      "shed_requests": 2,
                      "prefill_tokens": 640, "prefill_tokens_held": 512,
                      "per_tenant": {
                          "gold": {"prefills": 3, "evictions": 1,
                                   "requeues": 1, "admission_pauses": 0,
                                   "slo_deferrals": 2, "slo_misses": 1,
                                   "expired": 0, "shed_requests": 2,
                                   "finished": 3,
                                   "tokens_generated": 24},
                          "bronze": {"prefills": 1, "evictions": 0,
                                     "requeues": 0, "admission_pauses": 2,
                                     "slo_deferrals": 1, "slo_misses": 0,
                                     "expired": 0, "shed_requests": 0,
                                     "finished": 1,
                                     "tokens_generated": 8},
                      }}
        self.active = {1: object(), 2: object()}
        self.waiting = [object()]
        self.finished = [object(), object(), object()]
        self.allocator = _FakeAllocator()
        self.tenants = {"gold": object(), "bronze": object()}


class _FakeWeightPager:
    stats = {"fills": 12, "hits": 30, "waits": 2, "evictions": 8,
             "pattern_transitions": 1, "steals": 3}
    num_slots = 4


SERVE_ENGINE_FAMILIES = {
    "umap_serve_steps_total", "umap_serve_prefills_total",
    "umap_serve_evictions_total", "umap_serve_requeues_total",
    "umap_serve_admission_pauses_total", "umap_serve_active_requests",
    "umap_serve_waiting_requests", "umap_serve_finished_requests_total",
    "umap_serve_pool_occupancy_ratio",
    "umap_serve_slo_deferrals_total", "umap_serve_slo_misses_total",
    "umap_serve_expired_total", "umap_serve_victim_evictions_total",
    "umap_serve_cow_copies_total", "umap_serve_shared_pages_mapped_total",
    "umap_serve_prefix_hits_total", "umap_serve_prefix_drops_total",
    "umap_serve_peak_pages_used", "umap_serve_tenants",
    "umap_serve_shed_total", "umap_serve_paging_degraded",
    "umap_serve_prefill_tokens_total", "umap_serve_prefill_tokens_held_total",
}
SERVE_TENANT_FAMILIES = {
    "umap_serve_tenant_prefills_total", "umap_serve_tenant_evictions_total",
    "umap_serve_tenant_requeues_total",
    "umap_serve_tenant_admission_pauses_total",
    "umap_serve_tenant_slo_deferrals_total",
    "umap_serve_tenant_slo_misses_total", "umap_serve_tenant_expired_total",
    "umap_serve_tenant_shed_requests_total",
    "umap_serve_tenant_finished_total",
    "umap_serve_tenant_tokens_generated_total",
}
SERVE_KV_FAMILIES = {
    "umap_kv_pages_used", "umap_kv_pages_free", "umap_kv_occupancy_ratio",
    "umap_kv_sequences", "umap_kv_page_size_bytes",
    "umap_kv_auto_evicted_pages_total", "umap_kv_host_lock_contended_total",
    "umap_kv_cow_copies_total", "umap_kv_shared_pages",
    "umap_kv_shared_pages_mapped_total", "umap_kv_sequences_by_phase",
}
SERVE_WEIGHT_FAMILIES = {
    "umap_weight_fills_total", "umap_weight_hits_total",
    "umap_weight_waits_total", "umap_weight_evictions_total",
    "umap_weight_pattern_transitions_total", "umap_weight_steals_total",
    "umap_weight_slots",
}


class TestServeCollector:
    def test_engine_families(self):
        fams = families_of(ServeCollector(engine=_FakeEngine(), label="e"))
        assert set(fams) == SERVE_ENGINE_FAMILIES | SERVE_TENANT_FAMILIES
        assert fams["umap_serve_steps_total"].samples[0][2] == 10
        assert fams["umap_serve_active_requests"].samples[0][2] == 2
        assert fams["umap_serve_pool_occupancy_ratio"].samples[0][2] == 0.5

    def test_prefill_token_counters(self):
        fams = families_of(ServeCollector(engine=_FakeEngine(), label="e"))
        assert fams["umap_serve_prefill_tokens_total"].samples[0][2] == 640
        assert fams["umap_serve_prefill_tokens_held_total"] \
            .samples[0][2] == 512

    def test_per_tenant_labels_match_stats(self):
        """Every per-tenant family carries one sample per tenant, labeled
        ``tenant=``, whose value equals the engine's stats dict entry —
        the same parity contract as aggregate == sum(per_shard)."""
        eng = _FakeEngine()
        fams = families_of(ServeCollector(engine=eng, label="e"))
        per = eng.stats["per_tenant"]
        for fam_name in SERVE_TENANT_FAMILIES:
            fam = fams[fam_name]
            key = fam_name[len("umap_serve_tenant_"):-len("_total")]
            got = {lab["tenant"]: v for _, lab, v in fam.samples}
            assert got == {t: float(st[key]) for t, st in per.items()}, \
                fam_name

    def test_kv_families_and_phase_label(self):
        fams = families_of(ServeCollector(kv=_FakeKV(), label="e"))
        assert set(fams) == SERVE_KV_FAMILIES
        phases = {lab["phase"]: v for _, lab, v in
                  fams["umap_kv_sequences_by_phase"].samples}
        assert phases == {"stream": 2, "random": 1}

    def test_weight_pager_families(self):
        fams = families_of(ServeCollector(weight_pager=_FakeWeightPager(),
                                          label="w"))
        assert set(fams) == SERVE_WEIGHT_FAMILIES
        assert fams["umap_weight_slots"].samples[0][2] == 4
        assert fams["umap_weight_steals_total"].samples[0][2] == 3

    def test_all_sources_compose(self):
        fams = families_of(ServeCollector(
            engine=_FakeEngine(), kv=_FakeKV(),
            weight_pager=_FakeWeightPager(), label="all"))
        assert set(fams) == (SERVE_ENGINE_FAMILIES | SERVE_TENANT_FAMILIES
                             | SERVE_KV_FAMILIES | SERVE_WEIGHT_FAMILIES)


# --------------------------------------------------------- ProcessCollector


class TestProcessCollector:
    def test_families_present_and_sane(self):
        fams = families_of(ProcessCollector(label="self"))
        assert "umap_process_threads" in fams
        assert "umap_process_cpu_seconds_total" in fams
        assert "umap_process_uptime_seconds" in fams
        assert fams["umap_process_threads"].samples[0][2] >= 1
        assert fams["umap_process_cpu_seconds_total"].kind == "counter"
        if "umap_process_resident_memory_bytes" in fams:   # procfs platforms
            assert fams["umap_process_resident_memory_bytes"].samples[0][2] > 0
        if "umap_process_open_fds" in fams:
            assert fams["umap_process_open_fds"].samples[0][2] >= 1


# ------------------------------------------------------------- opt-in hooks


class TestServiceRegistration:
    def test_register_unregister_lifecycle(self):
        reg = TelemetryRegistry()
        r = make_region(tiered=True)
        try:
            names = r.service.register_telemetry(registry=reg, label="svc")
            assert names == ["pager:svc", "leases:svc", "tiering:svc/r0"]
            # idempotent: second call reports the same registration
            assert r.service.register_telemetry(registry=reg) == names
            assert set(reg.collector_names()) == set(names)
        finally:
            uunmap(r)
        assert reg.collector_names() == []            # close() unregistered

    def test_tiered_region_registered_after_optin(self):
        reg = TelemetryRegistry()
        r = make_region(tiered=False)
        try:
            r.service.register_telemetry(registry=reg, label="svc")
            assert not any(n.startswith("tiering:")
                           for n in reg.collector_names())
            npages = 16
            fast = HostArrayStore(np.zeros(npages * PS, np.uint8))
            slow = HostArrayStore(np.zeros(4 * npages * PS, np.uint8))
            r2 = umap(TieredStore(fast=fast, slow=slow, extent_size=4 * PS),
                      service=r.service)
            try:
                tier_names = [n for n in reg.collector_names()
                              if n.startswith("tiering:")]
                assert tier_names == [f"tiering:svc/r{r2.region_id}"]
            finally:
                uunmap(r2)
        finally:
            uunmap(r)


# ------------------------------------------------------------- exporter e2e


class TestExporterE2E:
    def test_scrape_over_http_ephemeral_port(self):
        reg = TelemetryRegistry()
        r = make_region()
        exp = TelemetryExporter(registry=reg, port=0).start()
        try:
            reg.register(PagerCollector(r.service, label="s"))
            for pno in range(8):
                r.read(pno * PS, 64)
            assert exp.port != 0
            resp = urllib.request.urlopen(exp.url, timeout=5)
            assert resp.status == 200
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            fams = parse_exposition(resp.read().decode())
            assert fams["umap_pager_demand_faults_total"]["type"] == "counter"
            samples = fams["umap_pager_demand_faults_total"]["samples"]
            assert samples[0][2] == 8
            # counters move between scrapes
            for pno in range(8, 12):
                r.read(pno * PS, 64)
            fams2 = parse_exposition(
                urllib.request.urlopen(exp.url, timeout=5).read().decode())
            assert fams2["umap_pager_demand_faults_total"]["samples"][0][2] == 12
        finally:
            exp.close()
            uunmap(r)

    def test_index_and_404(self):
        exp = TelemetryExporter(registry=TelemetryRegistry(), port=0).start()
        try:
            base = f"http://127.0.0.1:{exp.port}"
            idx = urllib.request.urlopen(base + "/", timeout=5)
            assert idx.status == 200 and b"/metrics" in idx.read()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/nope", timeout=5)
            assert ei.value.code == 404
        finally:
            exp.close()

    def test_close_stops_serving(self):
        exp = TelemetryExporter(registry=TelemetryRegistry(), port=0).start()
        url = exp.url
        exp.close()
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(url, timeout=1)


# -------------------------------------------------------- scrape-path rules


class TestScrapeNeverBlocks:
    def test_scrape_completes_while_all_shard_locks_held(self):
        """The acceptance rule made executable: with EVERY shard lock held
        by another thread (a worst-case fill/eviction convoy), a scrape
        still completes, because the collector path is lock-free."""
        reg = TelemetryRegistry()
        r = make_region(shards=4, tiered=True)
        try:
            reg.register(PagerCollector(r.service, label="s"))
            for region in r.service._regions.values():
                if region.tiered:
                    reg.register(TieringCollector(region.store, label="t"))
            for pno in range(8):
                r.read(pno * PS, 64)
            done = threading.Event()
            out = {}

            def scrape():
                out["text"] = reg.render()
                done.set()

            locks = [shard.lock for shard in r.service.shards]
            for lk in locks:
                lk.acquire()
            try:
                t = threading.Thread(target=scrape, daemon=True)
                t.start()
                assert done.wait(timeout=5.0), \
                    "scrape blocked on a shard lock"
            finally:
                for lk in locks:
                    lk.release()
            fams = parse_exposition(out["text"])
            assert fams["umap_pager_demand_faults_total"]["samples"][0][2] == 8
            assert "umap_tier_resident_extents" in fams
        finally:
            uunmap(r)

    def test_fault_storm_while_scraping(self):
        """Fault storm + concurrent scrape loop: reads stay byte-exact,
        every scrape completes, and afterwards the aggregate snapshot still
        sums the per-shard counters exactly (scraping perturbs nothing)."""
        npages, nthreads, buf_pages = 256, 4, 64
        data = (np.arange(npages * PS) % 251).astype(np.uint8)
        cfg = UMapConfig(page_size=PS, buffer_size=buf_pages * PS,
                         num_fillers=4, num_evictors=1, shards=4)
        r = umap(HostArrayStore(data), config=cfg)
        reg = TelemetryRegistry()
        reg.register(PagerCollector(r.service, label="s"))
        exp = TelemetryExporter(registry=reg, port=0).start()
        stop = threading.Event()
        errors = []
        scrapes = []

        def storm(seed):
            rng = np.random.default_rng(seed)
            try:
                for pno in rng.permutation(npages):
                    got = r.read(int(pno) * PS, 64)
                    want = data[int(pno) * PS:int(pno) * PS + 64]
                    if not np.array_equal(got, want):
                        errors.append(f"bad bytes at page {pno}")
            except Exception as e:                    # pragma: no cover
                errors.append(repr(e))

        def scraper():
            while not stop.is_set():
                try:
                    body = urllib.request.urlopen(exp.url, timeout=5).read()
                    scrapes.append(len(body))
                except Exception as e:                # pragma: no cover
                    errors.append(f"scrape: {e!r}")

        try:
            threads = [threading.Thread(target=storm, args=(i,), daemon=True)
                       for i in range(nthreads)]
            sc = threading.Thread(target=scraper, daemon=True)
            sc.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            stop.set()
            sc.join(timeout=10)
            assert not errors, errors[:5]
            assert len(scrapes) >= 2, "scraper never completed a scrape"
            # scrape text is well-formed under concurrency
            fams = parse_exposition(
                urllib.request.urlopen(exp.url, timeout=5).read().decode())
            assert fams["umap_pager_demand_faults_total"]["samples"][0][2] > 0
            # parity unperturbed: aggregate == per-shard sums (quiescent)
            from repro.core.pager import _SHARD_COUNTERS
            st = r.service.stats.snapshot()
            for key in _SHARD_COUNTERS:
                assert st[key] == sum(s[key] for s in st["per_shard"]), key
            # every touch is classified fault/hit/wait; eviction pressure
            # means pages can be re-faulted, so >= the touch count
            assert st["demand_faults"] + st["page_hits"] + st["wait_hits"] \
                >= npages * nthreads
        finally:
            stop.set()
            exp.close()
            uunmap(r)


# ------------------------------------------------------------ env autostart


class TestEnvAutostart:
    def _free_port(self):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def test_env_port_parsing(self):
        assert telemetry.env_port({}) == 0
        assert telemetry.env_port({"UMAP_TELEMETRY_PORT": ""}) == 0
        assert telemetry.env_port({"UMAP_TELEMETRY_PORT": "junk"}) == 0
        assert telemetry.env_port({"UMAP_TELEMETRY_PORT": "9100"}) == 9100

    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("UMAP_TELEMETRY_PORT", raising=False)
        r = make_region()
        try:
            assert r.service._telemetry is None
            assert telemetry.start_from_env() is None
        finally:
            uunmap(r)

    def test_autostart_registers_and_serves(self, monkeypatch):
        port = self._free_port()
        monkeypatch.setenv("UMAP_TELEMETRY_PORT", str(port))
        r = make_region()
        try:
            assert r.service._telemetry is not None
            exp = telemetry.env_exporter()
            assert exp is not None and exp.port == port
            fams = parse_exposition(
                urllib.request.urlopen(exp.url, timeout=5).read().decode())
            assert "umap_pager_demand_faults_total" in fams
            assert "umap_process_threads" in fams      # process collector too
        finally:
            uunmap(r)
            telemetry.shutdown()
            telemetry.default_registry().clear()
        # service close() removed its collectors from the default registry
        assert not any(n.startswith("pager:")
                       for n in telemetry.default_registry().collector_names())


# ------------------------------------------------------ ResilienceCollector


RESILIENCE_COUNTERS = {
    "umap_resilience_retries_total",
    "umap_resilience_retries_ok_total",
    "umap_resilience_retry_exhausted_total",
    "umap_resilience_deadline_exceeded_total",
    "umap_resilience_permanent_errors_total",
    "umap_resilience_breaker_rejections_total",
    "umap_resilience_hedges_total",
    "umap_resilience_hedge_wins_total",
    "umap_resilience_checksum_failures_total",
    "umap_resilience_breaker_opens_total",
    "umap_resilience_breaker_half_opens_total",
    "umap_resilience_breaker_closes_total",
}
RESILIENCE_GAUGES = {
    "umap_resilience_breaker_state",
    "umap_resilience_degraded_seconds",
}


class TestResilienceCollector:
    def _resilient_store(self):
        from repro.core import ChaosStore, ResilientStore
        chaos = ChaosStore(
            HostArrayStore((np.arange(16 * PS) % 251).astype(np.uint8)),
            seed=3)
        from repro.core.resilient import CircuitBreaker, RetryPolicy
        rs = ResilientStore(
            chaos, policy=RetryPolicy(retries=2, backoff_s=1e-4,
                                      max_backoff_s=1e-3),
            breaker=CircuitBreaker(threshold=5, reset_s=60.0))
        return rs, chaos

    def test_exact_family_names_and_types(self):
        rs, _ = self._resilient_store()
        fams = families_of(ResilienceCollector(rs, label="s"))
        assert set(fams) == RESILIENCE_COUNTERS | RESILIENCE_GAUGES
        for name in RESILIENCE_COUNTERS:
            assert fams[name].kind == "counter", name
        for name in RESILIENCE_GAUGES:
            assert fams[name].kind == "gauge", name

    def test_values_track_store_stats(self):
        rs, chaos = self._resilient_store()
        chaos.fail_next("read", count=2)
        rs.read_into(0, np.empty(PS, np.uint8))      # two retries absorbed
        chaos.kill()
        for _ in range(3):                           # trip the breaker
            try:
                rs.read_into(0, np.empty(PS, np.uint8))
            except OSError:
                pass
        fams = families_of(ResilienceCollector(rs, label="s"))
        snap = rs.resilience_stats()
        # exact parity with the wrapper snapshot, plus the known landmarks
        for key, mname in (("retries", "umap_resilience_retries_total"),
                           ("retries_ok", "umap_resilience_retries_ok_total"),
                           ("exhausted",
                            "umap_resilience_retry_exhausted_total"),
                           ("breaker_rejections",
                            "umap_resilience_breaker_rejections_total"),
                           ("breaker_opens",
                            "umap_resilience_breaker_opens_total")):
            assert fams[mname].samples[0][2] == snap[key], (key, mname)
        assert snap["retries"] >= 2 and snap["retries_ok"] == 1
        assert snap["breaker_opens"] == 1
        assert fams["umap_resilience_breaker_state"].samples[0][2] == 2  # open
        for _, labels, _ in fams["umap_resilience_retries_total"].samples:
            assert labels["source"] == "s"
        chaos.revive()

    def test_autoregistered_for_resilient_flat_region(self):
        reg = TelemetryRegistry()
        r = make_region(resilient_io=True)
        try:
            names = r.service.register_telemetry(registry=reg, label="svc")
            res_names = [n for n in names if n.startswith("resilience:")]
            assert res_names == ["resilience:svc/r0"]
            text = telemetry.render_registry(reg) \
                if hasattr(telemetry, "render_registry") else reg.render()
            assert "umap_resilience_retries_total" in text
        finally:
            uunmap(r)
        assert reg.collector_names() == []           # close() unregistered

    def test_autoregistered_per_tier(self):
        reg = TelemetryRegistry()
        r = make_region(tiered=True, resilient_io=True)
        try:
            names = r.service.register_telemetry(registry=reg, label="svc")
            res_names = sorted(n for n in names if n.startswith("resilience:"))
            assert res_names == ["resilience:svc/r0/fast",
                                 "resilience:svc/r0/slow"]
        finally:
            uunmap(r)
