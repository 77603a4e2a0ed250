"""Resilient I/O layer (DESIGN.md §17): retry policy + error taxonomy,
circuit breaker state machine, the ResilientStore wrapper (retries,
checksums, hedged reads, breaker gating), the ChaosStore harness, tiered
circuit-broken failover, quarantine auto-retry, and the bounded close
path.

Every ChaosStore schedule here is seeded or scripted (``fail_next`` /
``kill``), so failures replay deterministically; nothing in this file
depends on wall-clock beyond short breaker reset windows.
"""

import errno
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import (
    BreakerOpenError,
    ChaosStore,
    CircuitBreaker,
    CorruptPageError,
    HostArrayStore,
    ResilientStore,
    RetryPolicy,
    TieredStore,
    UMapConfig,
    umap,
    uunmap,
)
from repro.core.resilient import default_classify, iter_breakers, wrap_store

PAGE = 4096
EXTENT = 4 * PAGE
NPAGES = 64


def _data(nbytes: int) -> np.ndarray:
    return (np.arange(nbytes, dtype=np.int64) % 251).astype(np.uint8)


def _mem(nbytes: int, pattern: bool = True) -> HostArrayStore:
    return HostArrayStore(_data(nbytes) if pattern
                          else np.zeros(nbytes, np.uint8))


def _fast_policy(**kw) -> RetryPolicy:
    kw.setdefault("retries", 3)
    kw.setdefault("backoff_s", 1e-4)
    kw.setdefault("max_backoff_s", 1e-3)
    return RetryPolicy(**kw)


# ------------------------------------------------------- error taxonomy


class TestClassify:
    def test_transient_errors(self):
        assert default_classify(OSError(errno.EIO, "io"))
        assert default_classify(OSError(errno.EAGAIN, "again"))
        assert default_classify(OSError("no errno at all"))
        assert default_classify(TimeoutError("slow"))
        assert default_classify(CorruptPageError("crc"))
        assert default_classify(BreakerOpenError("open"))

    def test_permanent_errors(self):
        assert not default_classify(ValueError("bad arg"))
        assert not default_classify(TypeError("bad type"))
        assert not default_classify(KeyError("k"))
        assert not default_classify(NotImplementedError())
        assert not default_classify(PermissionError("denied"))
        assert not default_classify(FileNotFoundError("gone"))
        for eno in (errno.EACCES, errno.ENOENT, errno.ENOSPC, errno.EROFS):
            assert not default_classify(OSError(eno, "permanent"))

    def test_backoff_grows_and_caps(self):
        import random
        pol = RetryPolicy(backoff_s=0.01, max_backoff_s=0.04, jitter=0.0)
        rng = random.Random(0)
        sleeps = [pol.sleep_s(a, rng) for a in range(5)]
        assert sleeps[0] == pytest.approx(0.01)
        assert sleeps[1] == pytest.approx(0.02)
        assert sleeps[2] == pytest.approx(0.04)
        assert sleeps[4] == pytest.approx(0.04)      # capped

    def test_jitter_bounded(self):
        import random
        pol = RetryPolicy(backoff_s=0.01, max_backoff_s=0.01, jitter=0.5)
        rng = random.Random(7)
        for a in range(20):
            s = pol.sleep_s(a, rng)
            assert 0.01 <= s <= 0.015


# ------------------------------------------------------- circuit breaker


class TestCircuitBreaker:
    def test_threshold_trips_open(self):
        br = CircuitBreaker(threshold=3, reset_s=60.0)
        for _ in range(2):
            assert br.allow()
            br.record_failure()
        assert br.state == "closed"
        assert br.allow()
        br.record_failure()
        assert br.state == "open"
        assert br.tripped()
        assert not br.allow()

    def test_success_resets_failure_streak(self):
        br = CircuitBreaker(threshold=2, reset_s=60.0)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == "closed"      # streak broken: 1+1, not 2

    def test_half_open_probe_cycle_closes(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=1, reset_s=1.0, probes=2,
                            clock=lambda: clock[0])
        br.record_failure()
        assert br.state == "open" and not br.allow()
        clock[0] = 1.5
        assert not br.tripped()          # reset elapsed: route traffic again
        assert br.allow()                # probe 1 admitted, half-opens
        assert br.state == "half_open"
        br.record_success()
        assert br.allow()
        br.record_success()
        assert br.state == "closed"
        assert br.stats()["breaker_closes"] == 1

    def test_probe_failure_reopens(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=1, reset_s=1.0, probes=2,
                            clock=lambda: clock[0])
        br.record_failure()
        clock[0] = 1.5
        assert br.allow()
        br.record_failure()
        assert br.state == "open"
        assert br.stats()["breaker_opens"] == 2

    def test_half_open_bounds_concurrent_probes(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=1, reset_s=1.0, probes=2,
                            clock=lambda: clock[0])
        br.record_failure()
        clock[0] = 2.0
        assert br.allow() and br.allow()     # two probe slots
        assert not br.allow()                # third rejected
        br.record_success()
        assert br.allow()                    # slot released

    def test_listeners_see_every_edge(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=1, reset_s=1.0, probes=1,
                            clock=lambda: clock[0])
        edges = []
        br.add_listener(lambda old, new: edges.append((old, new)))
        br.record_failure()
        clock[0] = 1.5
        br.allow()
        br.record_success()
        assert edges == [("closed", "open"), ("open", "half_open"),
                         ("half_open", "closed")]
        br.remove_listener(edges.append)     # unknown fn: no-op

    def test_listener_exception_swallowed(self):
        br = CircuitBreaker(threshold=1)

        def bomb(old, new):
            raise RuntimeError("listener bug")

        br.add_listener(bomb)
        br.record_failure()                  # must not raise
        assert br.state == "open"

    def test_open_seconds_accumulates(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=1, reset_s=10.0, probes=1,
                            clock=lambda: clock[0])
        br.record_failure()                  # opens at t=0
        clock[0] = 4.0
        assert br.open_seconds() == pytest.approx(4.0)
        clock[0] = 12.0
        br.allow()                           # half-open at t=12
        br.record_success()                  # closed
        assert br.open_seconds() == pytest.approx(12.0)
        clock[0] = 20.0
        assert br.open_seconds() == pytest.approx(12.0)   # stopped counting


# ------------------------------------------------------- resilient store


class TestResilientStore:
    def test_passthrough_and_stats_shape(self):
        rs = ResilientStore(_mem(8 * PAGE), policy=_fast_policy())
        buf = np.empty(PAGE, np.uint8)
        assert rs.read_into(0, buf) == PAGE
        assert np.array_equal(buf, _data(PAGE))
        snap = rs.resilience_stats()
        for key in ("retries", "retries_ok", "exhausted", "permanent_errors",
                    "breaker_rejections", "hedges", "hedge_wins",
                    "checksum_failures", "deadline_exceeded", "breaker_state",
                    "breaker_opens", "degraded_seconds"):
            assert key in snap, key
        assert snap["retries"] == 0 and snap["breaker_state"] == 0

    def test_transient_errors_absorbed_by_retry(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=1)
        chaos.fail_next("read", count=2)
        rs = ResilientStore(chaos, policy=_fast_policy())
        buf = np.empty(PAGE, np.uint8)
        assert rs.read_into(0, buf) == PAGE
        assert np.array_equal(buf, _data(PAGE))
        snap = rs.resilience_stats()
        assert snap["retries"] == 2 and snap["retries_ok"] == 1
        assert snap["exhausted"] == 0

    def test_retry_budget_exhausted_raises(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=1)
        chaos.fail_next("read", count=10)
        rs = ResilientStore(chaos, policy=_fast_policy(retries=2))
        with pytest.raises(OSError):
            rs.read_into(0, np.empty(PAGE, np.uint8))
        snap = rs.resilience_stats()
        assert snap["exhausted"] == 1 and snap["retries"] == 2

    def test_permanent_error_never_retried(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=1)
        chaos.fail_next("read", count=1, permanent=True)
        rs = ResilientStore(chaos, policy=_fast_policy())
        with pytest.raises(PermissionError):
            rs.read_into(0, np.empty(PAGE, np.uint8))
        snap = rs.resilience_stats()
        assert snap["permanent_errors"] == 1 and snap["retries"] == 0
        assert chaos.chaos_stats()["reads_attempted"] == 1

    def test_deadline_bounds_total_backoff(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=1)
        chaos.fail_next("read", count=100)
        rs = ResilientStore(chaos, policy=RetryPolicy(
            retries=100, backoff_s=0.05, max_backoff_s=0.05,
            deadline_s=0.12))
        t0 = time.monotonic()
        with pytest.raises(OSError):
            rs.read_into(0, np.empty(PAGE, np.uint8))
        assert time.monotonic() - t0 < 1.0
        snap = rs.resilience_stats()
        assert snap["deadline_exceeded"] == 1 and snap["exhausted"] == 1

    def test_breaker_trips_then_fails_fast(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=1)
        chaos.kill()
        rs = ResilientStore(chaos, policy=_fast_policy(retries=0),
                            breaker=CircuitBreaker(threshold=2, reset_s=60.0))
        buf = np.empty(PAGE, np.uint8)
        for _ in range(2):
            with pytest.raises(OSError):
                rs.read_into(0, buf)
        attempted = chaos.chaos_stats()["reads_attempted"]
        with pytest.raises(BreakerOpenError):
            rs.read_into(0, buf)
        # fail-fast: the dead store was NOT touched again
        assert chaos.chaos_stats()["reads_attempted"] == attempted
        assert rs.resilience_stats()["breaker_rejections"] == 1

    def test_breaker_recovery_closes_after_probes(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=1)
        chaos.kill()
        rs = ResilientStore(chaos, policy=_fast_policy(retries=0),
                            breaker=CircuitBreaker(threshold=1, reset_s=0.05,
                                                   probes=2))
        buf = np.empty(PAGE, np.uint8)
        with pytest.raises(OSError):
            rs.read_into(0, buf)
        assert rs.breaker.state == "open"
        chaos.revive()
        time.sleep(0.06)
        rs.read_into(0, buf)
        rs.read_into(0, buf)
        assert rs.breaker.state == "closed"
        assert np.array_equal(buf, _data(PAGE))

    def test_checksum_catches_bit_flip(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=5)
        rs = ResilientStore(chaos, policy=_fast_policy(),
                            verify_reads=True, checksum_block=PAGE)
        buf = np.empty(PAGE, np.uint8)
        rs.read_into(0, buf)                         # records the block CRC
        chaos.bit_flip_rate = 1.0                    # every read now corrupts
        with pytest.raises(OSError):                 # retries all corrupt too
            rs.read_into(0, np.empty(PAGE, np.uint8))
        snap = rs.resilience_stats()
        assert snap["checksum_failures"] >= 1

    def test_checksum_retry_recovers_one_shot_corruption(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=5, bit_flip_rate=0.0)
        rs = ResilientStore(chaos, policy=_fast_policy(),
                            verify_reads=True, checksum_block=PAGE)
        good = np.empty(PAGE, np.uint8)
        rs.read_into(0, good)
        # corrupt exactly one read, then heal: the retry must re-read clean
        chaos.bit_flip_rate = 1.0
        orig = chaos._maybe_flip

        def flip_once(bufs):
            orig(bufs)
            chaos.bit_flip_rate = 0.0    # heal after this one corruption

        chaos._maybe_flip = flip_once
        buf = np.empty(PAGE, np.uint8)
        rs.read_into(0, buf)
        assert np.array_equal(buf, _data(PAGE))
        snap = rs.resilience_stats()
        assert snap["checksum_failures"] == 1 and snap["retries_ok"] == 1

    def test_checksum_written_blocks_verified(self):
        rs = ResilientStore(_mem(8 * PAGE, pattern=False),
                            policy=_fast_policy(), verify_reads=True,
                            checksum_block=PAGE)
        payload = np.full(PAGE, 7, np.uint8)
        rs.write_from(PAGE, payload)
        # corrupt the inner store directly behind the wrapper's back
        rs.inner._data[PAGE + 100] ^= 0xFF
        with pytest.raises(OSError):
            rs.read_into(PAGE, np.empty(PAGE, np.uint8))
        assert rs.resilience_stats()["checksum_failures"] >= 1

    def test_partial_write_invalidates_block_crc(self):
        rs = ResilientStore(_mem(8 * PAGE, pattern=False),
                            policy=_fast_policy(), verify_reads=True,
                            checksum_block=PAGE)
        rs.write_from(0, np.full(PAGE, 1, np.uint8))
        rs.write_from(100, np.full(8, 2, np.uint8))      # partial: CRC dropped
        buf = np.empty(PAGE, np.uint8)
        rs.read_into(0, buf)                             # re-records, no raise
        assert buf[100] == 2 and buf[0] == 1

    def test_hedged_read_waits_out_latency_spike(self):
        # Primary read stalls 0.5s inside the store; the spike clears at
        # 30ms, the hedge fires at 80ms against the healed store and wins
        # long before the stuck primary returns.
        chaos = ChaosStore(_mem(8 * PAGE), seed=2,
                           latency_spike_rate=1.0, latency_spike_s=0.5)
        rs = ResilientStore(chaos, policy=_fast_policy(),
                            hedge_delay_s=0.08, name="hedge-test")

        def heal():
            time.sleep(0.03)
            chaos.latency_spike_rate = 0.0

        t = threading.Thread(target=heal)
        t.start()
        buf = np.empty(PAGE, np.uint8)
        t0 = time.monotonic()
        rs.read_into(0, buf)
        dt = time.monotonic() - t0
        t.join()
        rs.close()
        assert np.array_equal(buf, _data(PAGE))
        snap = rs.resilience_stats()
        assert snap["hedges"] >= 1 and snap["hedge_wins"] >= 1
        assert dt < 0.4, "hedge should beat the spiked primary"

    def test_batch_ops_route_through_wrapper(self):
        chaos = ChaosStore(_mem(8 * PAGE), seed=3)
        chaos.fail_next("write", count=1)
        rs = ResilientStore(chaos, policy=_fast_policy())
        bufs = [np.full(PAGE, 9, np.uint8) for _ in range(2)]
        assert rs.write_from_batch(0, bufs) == 2 * PAGE
        assert rs.resilience_stats()["retries_ok"] == 1
        out = [np.empty(PAGE, np.uint8) for _ in range(2)]
        assert rs.read_into_batch(0, out) == 2 * PAGE
        assert all((o == 9).all() for o in out)

    def test_wrap_store_idempotent_and_tier_aware(self):
        cfg = UMapConfig(resilient_io=True)
        flat = wrap_store(_mem(8 * PAGE), cfg)
        assert isinstance(flat, ResilientStore)
        assert wrap_store(flat, cfg) is flat
        ts = TieredStore(_mem(2 * EXTENT, pattern=False), _mem(8 * EXTENT),
                         extent_size=EXTENT)
        wrapped = wrap_store(ts, cfg)
        assert wrapped is ts                      # identity preserved
        assert isinstance(ts.fast, ResilientStore)
        assert isinstance(ts.slow, ResilientStore)
        assert len(list(iter_breakers(ts))) == 2
        wrap_store(ts, cfg)                       # second wrap: no double-wrap
        assert not isinstance(ts.fast.inner, ResilientStore)


# ------------------------------------------------------------ chaos store


class TestChaosStore:
    def test_seeded_schedule_replays(self):
        def run(seed):
            ch = ChaosStore(_mem(32 * PAGE), seed=seed, read_error_rate=0.3)
            outcomes = []
            for i in range(50):
                try:
                    ch.read_into(i % 8 * PAGE, np.empty(PAGE, np.uint8))
                    outcomes.append("ok")
                except OSError:
                    outcomes.append("err")
            return outcomes

        assert run(42) == run(42)
        assert run(42) != run(43)       # astronomically unlikely to collide

    def test_kill_revive(self):
        ch = ChaosStore(_mem(8 * PAGE), seed=0)
        buf = np.empty(PAGE, np.uint8)
        ch.read_into(0, buf)
        ch.kill()
        assert ch.dead
        with pytest.raises(OSError):
            ch.read_into(0, buf)
        with pytest.raises(OSError):
            ch.write_from(0, buf)
        ch.revive()
        ch.read_into(0, buf)
        assert ch.chaos_stats()["outage_rejections"] == 2

    def test_torn_write_persists_prefix_then_raises(self):
        inner = _mem(8 * PAGE, pattern=False)
        ch = ChaosStore(inner, seed=9, torn_write_rate=1.0)
        with pytest.raises(OSError):
            ch.write_from(0, np.full(2 * PAGE, 5, np.uint8))
        st = ch.chaos_stats()
        assert st["torn_writes"] == 1
        written = int((inner._data[:2 * PAGE] == 5).sum())
        assert 0 <= written < 2 * PAGE            # strict prefix, never all

    def test_bit_flip_corrupts_exactly_one_bit(self):
        ch = ChaosStore(_mem(8 * PAGE), seed=4, bit_flip_rate=1.0)
        buf = np.empty(PAGE, np.uint8)
        ch.read_into(0, buf)
        diff = buf ^ _data(PAGE)
        assert int(np.unpackbits(diff).sum()) == 1
        assert ch.chaos_stats()["bit_flips"] == 1

    def test_fail_next_is_exact(self):
        ch = ChaosStore(_mem(8 * PAGE), seed=0)
        ch.fail_next("read", count=2)
        buf = np.empty(PAGE, np.uint8)
        for _ in range(2):
            with pytest.raises(OSError):
                ch.read_into(0, buf)
        ch.read_into(0, buf)                      # third op clean
        ch.write_from(0, buf)                     # writes unaffected
        st = ch.chaos_stats()
        assert st["injected_read_errors"] == 2
        assert st["injected_write_errors"] == 0

    def test_latency_spike_sleeps(self):
        ch = ChaosStore(_mem(8 * PAGE), seed=0, latency_spike_rate=1.0,
                        latency_spike_s=0.05)
        t0 = time.monotonic()
        ch.read_into(0, np.empty(PAGE, np.uint8))
        assert time.monotonic() - t0 >= 0.05
        assert ch.chaos_stats()["latency_spikes"] == 1


# ------------------------------------------------- tiered failover (§17.5)


def _tiered_chaos(fast_extents: int = 8, **chaos_kw):
    slow = _mem(NPAGES * PAGE)
    chaos = ChaosStore(_mem(fast_extents * EXTENT, pattern=False),
                       seed=13, **chaos_kw)
    ts = TieredStore(chaos, slow, extent_size=EXTENT, promote_on_read=True)
    cfg = UMapConfig(page_size=PAGE, buffer_size=16 * PAGE,
                     resilient_io=True, io_retries=4,
                     retry_backoff_s=0.002, retry_max_backoff_s=0.02,
                     breaker_threshold=3, breaker_reset_s=0.25)
    region = umap(ts, config=cfg)
    return region, ts, chaos


class TestTieredFailover:
    def test_fast_outage_degrades_to_slow_byte_exact(self):
        region, ts, chaos = _tiered_chaos()
        try:
            ref = _data(NPAGES * PAGE)
            # warm twice the 16-page buffer: pages 0-15 are then read again
            # from the store (the fast tier) during the outage, not served
            # from the page buffer
            for p in range(32):
                assert np.array_equal(region.read(p * PAGE, PAGE),
                                      ref[p * PAGE:(p + 1) * PAGE])
            assert ts.resident_extents()          # warm promoted something
            chaos.kill()
            # every read during the outage: correct bytes, zero exceptions
            for p in range(32):
                assert np.array_equal(region.read(p * PAGE, PAGE),
                                      ref[p * PAGE:(p + 1) * PAGE]), p
            assert ts.tier_failovers > 0
            assert region.service.open_breakers() == 1
            assert region.service.stats.io_errors == 0
        finally:
            chaos.revive()
            uunmap(region)

    def test_promotion_refused_while_tripped_resumes_after(self):
        region, ts, chaos = _tiered_chaos()
        try:
            region.read(0, PAGE)                  # warm
            chaos.kill()
            for p in range(16):
                region.read(p * PAGE, PAGE)
            assert ts.promote(5) is False         # tripped: no admissions
            chaos.revive()
            time.sleep(0.3)                       # reset window elapses
            for _ in range(3):
                for p in range(16):
                    region.read(p * PAGE, PAGE)
            assert ts.fast.breaker.state == "closed"
            assert ts.resident_extents()          # re-admitted
        finally:
            uunmap(region)

    def test_dirty_resident_bytes_survive_outage(self):
        """Dirty fast-tier extents hold the ONLY copy: routing must keep
        pointing at fast (errors propagate -> quarantine) rather than
        silently serving stale slow-tier bytes."""
        region, ts, chaos = _tiered_chaos()
        try:
            region.read(0, PAGE)
            assert ts.promote(0) or 0 in dict.fromkeys(ts.resident_extents())
            # dirty extent 0 via direct store write (bypasses pager cache)
            ts.write_from(0, np.full(PAGE, 77, np.uint8))
            assert ts.tier_stats()["dirty_extents"] >= 1
            chaos.kill()
            # a direct read of the dirty extent must NOT serve slow bytes
            with pytest.raises(OSError):
                ts.read_into(0, np.empty(PAGE, np.uint8))
            chaos.revive()
            time.sleep(0.3)
            buf = np.empty(PAGE, np.uint8)
            ts.read_into(0, buf)
            assert (buf == 77).all()              # the one true copy survived
        finally:
            uunmap(region)


# ------------------------------------------- quarantine auto-retry (§17.4)


class TestQuarantineRetry:
    def _quarantined_region(self):
        inner = _mem(32 * PAGE)
        chaos = ChaosStore(inner, seed=7)
        cfg = UMapConfig(page_size=PAGE, buffer_size=8 * PAGE,
                         resilient_io=True, io_retries=1,
                         retry_backoff_s=0.001, retry_deadline_s=0.2,
                         breaker_threshold=2, breaker_reset_s=0.2,
                         writeback_retries=1)
        region = umap(chaos, config=cfg)
        for p in range(4):
            region.write(p * PAGE, np.full(PAGE, 42, np.uint8))
        chaos.kill()
        with pytest.raises(IOError):
            region.service.flush_region(region)
        assert region.service.stats.quarantined_pages == 4
        return region, chaos, inner

    def test_manual_retry_quarantined(self):
        region, chaos, inner = self._quarantined_region()
        svc = region.service
        try:
            chaos.revive()
            time.sleep(0.25)                      # breaker reset window
            n = svc.retry_quarantined(region)
            assert n == 4
            deadline = time.time() + 3
            while time.time() < deadline and svc.stats.quarantined_pages:
                time.sleep(0.02)
            s = svc.stats
            assert s.quarantined_pages == 0
            assert s.quarantine_retries == 4
            svc.flush_region(region)
            chk = np.empty(PAGE, np.uint8)
            inner.read_into(0, chk)
            assert (chk == 42).all()              # zero lost pages
        finally:
            uunmap(region)

    def test_retry_while_store_still_dead_requarantines(self):
        region, chaos, _ = self._quarantined_region()
        svc = region.service
        try:
            assert svc.retry_quarantined(region) == 4
            deadline = time.time() + 3
            while time.time() < deadline and svc.stats.quarantined_pages < 4:
                time.sleep(0.02)
            assert svc.stats.quarantined_pages == 4   # failed again: back in
            assert svc.stats.quarantine_retries == 4
        finally:
            chaos.revive()
            time.sleep(0.25)                      # let the breaker half-open
            svc.retry_quarantined(region)
            deadline = time.time() + 3
            while time.time() < deadline and svc.stats.quarantined_pages:
                time.sleep(0.02)
            uunmap(region)

    def test_breaker_close_auto_invokes_retry(self):
        region, chaos, inner = self._quarantined_region()
        svc = region.service
        try:
            # trip the breaker with failing reads, then heal the store and
            # drive probe traffic: the open->closed edge must re-post the
            # quarantined pages with NO manual retry_quarantined call.
            for p in range(8, 12):
                with pytest.raises(IOError):
                    region.read(p * PAGE, PAGE)
            assert next(iter_breakers(region.store)).state == "open"
            chaos.revive()
            time.sleep(0.25)
            for p in range(8, 12):
                region.read(p * PAGE, PAGE)
            deadline = time.time() + 3
            while time.time() < deadline:
                s = svc.stats
                if s.quarantined_pages == 0 and s.quarantine_retries > 0:
                    break
                time.sleep(0.02)
            s = svc.stats
            assert s.quarantine_retries == 4
            assert s.quarantined_pages == 0
            svc.flush_region(region)
            chk = np.empty(PAGE, np.uint8)
            inner.read_into(0, chk)
            assert (chk == 42).all()
        finally:
            uunmap(region)

    def test_retry_skips_pinned_and_clean_pages(self):
        region, chaos, _ = self._quarantined_region()
        svc = region.service
        try:
            chaos.revive()
            time.sleep(0.25)                      # breaker reset window
            lease = svc.lease_page(region, 0)     # pins quarantined page 0
            try:
                n = svc.retry_quarantined(region)
                assert n == 3                     # pinned page skipped
            finally:
                lease.release()
            deadline = time.time() + 3
            while time.time() < deadline and svc.stats.quarantined_pages > 1:
                time.sleep(0.02)
            assert svc.stats.quarantined_pages == 1
            assert svc.retry_quarantined(region) == 1
            deadline = time.time() + 3
            while time.time() < deadline and svc.stats.quarantined_pages:
                time.sleep(0.02)
        finally:
            uunmap(region)


# ------------------------------------------------- bounded close (§17.7)


class TestBoundedClose:
    def test_close_mid_stall_returns_and_warns(self):
        """service.close() during an in-flight fill stalled inside the
        store must return within the join deadline, warn loudly, and name
        the leaked thread — not hang until the store call finishes."""
        chaos = ChaosStore(_mem(32 * PAGE), seed=1,
                           latency_spike_rate=1.0, latency_spike_s=3.0)
        cfg = UMapConfig(page_size=PAGE, buffer_size=8 * PAGE)
        region = umap(chaos, config=cfg)
        svc = region.service
        svc.request_fills(region, [0, 1])
        time.sleep(0.1)                           # filler now inside sleep
        t0 = time.monotonic()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            svc.close(join_timeout_s=0.3)
        dt = time.monotonic() - t0
        assert dt < 2.0, f"close took {dt:.1f}s — unbounded join"
        assert svc.leaked_threads, "leaked filler not recorded"
        assert any("umap-filler" in name for name in svc.leaked_threads)
        msgs = [str(w.message) for w in caught]
        assert any("leak" in m or "thread" in m for m in msgs), msgs

    def test_clean_close_leaks_nothing(self):
        region = umap(_mem(32 * PAGE), config=UMapConfig(
            page_size=PAGE, buffer_size=8 * PAGE))
        svc = region.service
        region.read(0, PAGE)
        uunmap(region)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            svc.close(join_timeout_s=5.0)
        assert svc.leaked_threads == []
        assert not caught

