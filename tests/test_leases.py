"""Zero-copy leases + coalesced write-back pipeline (DESIGN.md §13).

Covers: the lease life-cycle (zero-copy aliasing, read-only read leases,
dirty-exactly-once write leases, idempotent release, pin-blocks-eviction),
``lease_run`` length caps and cleanup-on-error, the copy-backed
``zero_copy_leases=False`` mode, the concurrent-lease vs
``flush_region(evict=True)`` closing-gate interaction, the
pinned-at-dequeue cleaner regression (satellite fix), write-back
coalescing counters, ``write_from_batch`` byte-exactness across all five
stores, and the zero-staging-copy witnesses for the converted consumers
(weight pager + paged KV).
"""

import threading
import time

import numpy as np
import pytest

from repro.core import (
    FileStore,
    HostArrayStore,
    MultiFileStore,
    PageState,
    RemoteStore,
    SyntheticStore,
    UMapConfig,
    umap,
    uunmap,
)


def _pattern(n: int, offset: int = 0) -> np.ndarray:
    return (np.arange(offset, offset + n, dtype=np.int64) % 251).astype(np.uint8)


def _make_region(npages=32, ps=4096, slots=None, **cfg_kw):
    store = HostArrayStore(_pattern(npages * ps).copy())
    cfg = UMapConfig(page_size=ps, buffer_size=(slots or npages) * ps,
                     num_fillers=2, num_evictors=2, shards=4, **cfg_kw)
    return store, umap(store, config=cfg)


# ------------------------------------------------------------ lease life-cycle


def test_read_lease_is_zero_copy_and_read_only():
    ps = 4096
    store, r = _make_region(ps=ps)
    try:
        with r.lease(3) as ls:
            assert np.array_equal(ls.view, _pattern(ps, 3 * ps))
            assert ls.zero_copy
            with pytest.raises(ValueError):
                ls.view[0] = 1                   # read lease: immutable view
            # genuinely aliases the buffer slot — no memcpy happened
            e = r.service.table.get((r.region_id, 3))
            slot = r.service.buffer.slot_view(e.slot, ps)
            assert np.shares_memory(ls.view, slot)
        assert r.stats()["leases"] == 1
    finally:
        uunmap(r)


def test_write_lease_marks_dirty_exactly_once_and_release_is_idempotent():
    ps = 4096
    store, r = _make_region(ps=ps)
    try:
        before = r.service.table.dirty_count
        ls = r.lease(2, write=True)
        ls.view[:64] = 77
        assert r.service.table.dirty_count == before  # dirty only on release
        ls.release()
        assert r.service.table.dirty_count == before + 1
        ls.release()                                  # idempotent
        ls.release()
        assert r.service.table.dirty_count == before + 1
        e = r.service.table.get((r.region_id, 2))
        assert e.pins == 0 and e.leases == 0
        r.flush()
        chk = np.empty(64, np.uint8)
        store.read_into(2 * ps, chk)
        assert (chk == 77).all()
    finally:
        uunmap(r)


def test_lease_pin_blocks_eviction_and_is_counted():
    """A leased page must survive arbitrary capacity churn; the skipped
    victim picks surface as lease_blocked_evictions."""
    npages, ps, slots = 64, 4096, 8
    store, r = _make_region(npages=npages, ps=ps, slots=slots)
    try:
        with r.lease(0) as ls:
            for pno in range(1, npages):          # storm past the buffer
                assert r.read(pno * ps, 64)[0] == _pattern(1, pno * ps)[0]
            # still resident, still byte-exact, never recycled
            e = r.service.table.get((r.region_id, 0))
            assert e is not None and e.state is PageState.PRESENT
            assert np.array_equal(ls.view, _pattern(ps))
        st = r.stats()
        assert st["lease_blocked_evictions"] >= 1
        assert st["evictions"] > 0                # churn really happened
    finally:
        uunmap(r)


def test_lease_run_posts_fills_and_caps_length():
    npages, ps = 32, 4096
    store, r = _make_region(npages=npages, ps=ps)
    try:
        with r.lease_run(4, 6) as run:
            assert len(run) == 6
            for i, v in enumerate(run.views):
                assert np.array_equal(v, _pattern(ps, (4 + i) * ps))
        cap = min(r.service.config.max_lease_run,
                  r.service.buffer.num_slots // 2)
        with pytest.raises(ValueError):
            r.service.lease_run(r, 0, cap + 1)
        with pytest.raises(IndexError):
            r.lease_run(npages - 2, 4)            # falls off the region
        assert r.stats()["leases"] == 6
    finally:
        uunmap(r)


def test_copy_backed_mode_keeps_lease_api_without_aliasing():
    ps = 4096
    store, r = _make_region(ps=ps, zero_copy_leases=False)
    try:
        with r.lease(1) as ls:
            assert not ls.zero_copy
            assert np.array_equal(ls.view, _pattern(ps, ps))
        with r.lease(1, write=True) as ls:
            ls.view[:32] = 55
        assert (r.read(ps, 32) == 55).all()       # written back on release
        assert r.stats()["leases"] == 2
    finally:
        uunmap(r)


def test_concurrent_lease_vs_evicting_flush_closing_gate():
    """Leases racing region close: either the lease wins (and close waits
    for its pin) or the closing gate raises — never a ghost page, never a
    view into a recycled slot."""
    npages, ps = 16, 4096
    for _ in range(5):
        store = HostArrayStore(_pattern(npages * ps).copy())
        cfg = UMapConfig(page_size=ps, buffer_size=npages * ps,
                         num_fillers=2, num_evictors=2, shards=4)
        from repro.core import PagingService
        svc = PagingService(cfg)
        r = umap(store, service=svc)
        rid = r.region_id
        stop = threading.Event()
        raised = []

        def leaser():
            rng = np.random.default_rng(0)
            while not stop.is_set():
                pno = int(rng.integers(0, npages))
                try:
                    with r.lease(pno) as ls:
                        assert ls.view[0] == _pattern(1, pno * ps)[0]
                except RuntimeError as exc:       # closing gate
                    raised.append(str(exc))
                    return

        ts = [threading.Thread(target=leaser) for _ in range(3)]
        [t.start() for t in ts]
        time.sleep(0.01)
        r.close()                                  # evicting flush + unregister
        stop.set()
        [t.join(timeout=30) for t in ts]
        assert not any(t.is_alive() for t in ts), "leaser hung against close"
        assert all("closing" in m for m in raised)
        assert not svc.table.region_entries(rid), "ghost page survived close"
        svc.close()


# --------------------------------------------- pinned-at-dequeue (satellite fix)


def test_cleaner_refuses_page_pinned_after_posting():
    """Regression: a page posted to the cleaner queue and *then* pinned
    (an in-flight lease) must not be written back mid-mutation — the
    evictor re-checks pins at dequeue time, reverts the page to PRESENT,
    and leaves it dirty for a later repost."""
    ps = 4096
    store, r = _make_region(ps=ps)
    svc = r.service
    try:
        r.write(0, np.full(ps, 9, np.uint8))       # page 0 resident + dirty
        key = (r.region_id, 0)
        e = svc.table.get(key)
        ls = r.lease(0, write=True)
        ls.view[:16] = 123                          # mid-mutation
        writes_before = store.num_writes
        # Simulate the racing poster: CLEANING + queued while pinned (the
        # in-tree posters check pins at post time; the dequeue-time check
        # is the defense for any interleaving that slips past them).
        shard = svc._shard_of(key)
        with svc._locked(shard):
            e.state = PageState.CLEANING
            e.event.clear()
            svc._clean_q.put(("clean", e))
        deadline = time.time() + 5.0
        while e.state is PageState.CLEANING and time.time() < deadline:
            time.sleep(0.001)
        assert e.state is PageState.PRESENT, "cleaner never handled the page"
        assert store.num_writes == writes_before, \
            "cleaner wrote back a lease-pinned page mid-mutation"
        assert e.dirty, "dirty bit lost on the deferred page"
        assert r.stats()["lease_blocked_evictions"] >= 1
        ls.release()
        r.flush()                                   # now it may drain
        chk = np.empty(16, np.uint8)
        store.read_into(0, chk)
        assert (chk == 123).all()
    finally:
        uunmap(r)


# ------------------------------------------------------- write-back coalescing


def test_flush_coalesces_adjacent_dirty_pages():
    npages, ps = 32, 4096
    store, r = _make_region(npages=npages, ps=ps)
    try:
        for pno in range(8):
            r.write(pno * ps, np.full(ps, 7, np.uint8))
        writes_before = store.num_writes
        r.flush()
        st = r.stats()
        assert store.num_writes - writes_before < 8, \
            "flush issued one store write per dirty page"
        assert st["coalesced_writebacks"] >= 1
        assert st["writeback_pages"] >= 8
        assert st["writebacks"] == 8               # per-page accounting intact
        chk = np.empty(8 * ps, np.uint8)
        store.read_into(0, chk)
        assert (chk == 7).all()
    finally:
        uunmap(r)


def test_max_writeback_batch_1_restores_per_page_writes():
    npages, ps = 16, 4096
    store, r = _make_region(npages=npages, ps=ps, max_writeback_batch=1)
    try:
        for pno in range(6):
            r.write(pno * ps, np.full(ps, 3, np.uint8))
        writes_before = store.num_writes
        r.flush()
        st = r.stats()
        assert store.num_writes - writes_before == 6
        assert st["coalesced_writebacks"] == 0
    finally:
        uunmap(r)


def test_dirty_storm_drains_batched_and_byte_exact():
    """Writers + watermark pressure + batched cleaners: every dirty page
    lands byte-exact, with the batched path actually engaged."""
    npages, ps = 64, 4096
    base = _pattern(npages * ps)
    store = HostArrayStore(base.copy())
    cfg = UMapConfig(page_size=ps, buffer_size=npages * ps, num_fillers=4,
                     num_evictors=2, shards=8,
                     evict_high_water=0.3, evict_low_water=0.1)
    r = umap(store, config=cfg)
    try:
        def writer(tid):
            lo = tid * 16
            for rep in range(3):
                for i in range(16):
                    r.write((lo + i) * ps, np.full(ps, 100 + tid, np.uint8))

        ts = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
        assert not any(t.is_alive() for t in ts)
        r.flush()
        st = r.stats()
        assert st["coalesced_writebacks"] >= 1, st
        for tid in range(4):
            chk = np.empty(16 * ps, np.uint8)
            store.read_into(tid * 16 * ps, chk)
            assert (chk == 100 + tid).all(), f"writer {tid} data torn"
    finally:
        uunmap(r)


# ------------------------------------- write_from_batch across all five stores


def _check_batch_write(store, total_bytes):
    """write_from_batch must byte-match a reference write_from, in ONE op."""
    rng = np.random.default_rng(7)
    payload = rng.integers(0, 256, size=total_bytes, dtype=np.uint8).view(np.uint8)
    cuts = [0, total_bytes // 5, total_bytes // 2, total_bytes]
    bufs = [payload[lo:hi] for lo, hi in zip(cuts, cuts[1:])]
    ops_before = getattr(store, "num_writes", 0)
    done = store.write_from_batch(0, bufs)
    assert done == total_bytes
    assert store.num_writes == ops_before + 1, \
        f"{type(store).__name__} batched write issued {store.num_writes - ops_before} ops"
    back = np.empty(total_bytes, np.uint8)
    store.read_into(0, back)
    assert np.array_equal(back, payload), type(store).__name__


def test_write_from_batch_hostarray():
    _check_batch_write(HostArrayStore(np.zeros(1 << 16, np.uint8)), 1 << 15)


def test_write_from_batch_file(tmp_path):
    st = FileStore(str(tmp_path / "f.bin"), size=1 << 16, create=True)
    try:
        _check_batch_write(st, 1 << 15)
    finally:
        st.close()


def test_write_from_batch_multifile(tmp_path):
    members = [FileStore(str(tmp_path / f"m{i}.bin"), size=1 << 14, create=True)
               for i in range(3)]
    st = MultiFileStore([(m, 0, 1 << 14) for m in members])
    try:
        _check_batch_write(st, 3 * (1 << 14))     # spans all three extents
    finally:
        st.close()


def test_write_from_batch_remote():
    st = RemoteStore(HostArrayStore(np.zeros(1 << 16, np.uint8)),
                     latency_s=1e-4, bandwidth_Bps=1e9)
    _check_batch_write(st, 1 << 15)
    assert st.inner.num_writes == 1               # one inner op too


def test_write_from_batch_synthetic():
    st = SyntheticStore(1 << 16, lambda off, buf: buf.fill(0))
    _check_batch_write(st, 1 << 15)


def test_write_from_batch_default_loop_matches():
    """The base-class default (loop of write_from) stays byte-compatible."""
    st = HostArrayStore(np.zeros(1 << 12, np.uint8))
    payload = _pattern(1 << 12)
    from repro.core import BackingStore
    BackingStore.write_from_batch(st, 0, [payload[:100], payload[100:]])
    back = np.empty(1 << 12, np.uint8)
    st.read_into(0, back)
    assert np.array_equal(back, payload)


def test_three_concurrent_lease_runs_dont_deadlock():
    """Regression (review finding): with the buffer small enough that three
    concurrent runs cannot all hold their pins, incomplete runs must abort
    and retry (releasing pins) rather than deadlock."""
    npages, ps, slots = 16, 4096, 8
    store, r = _make_region(npages=npages, ps=ps, slots=slots)
    cap = r.service.buffer.num_slots // 2          # == 4: 3*4 > 8 slots
    errors = []
    barrier = threading.Barrier(3)

    def worker(tid):
        try:
            barrier.wait()
            for rep in range(10):
                first = (tid * 5 + rep) % (npages - cap)
                with r.lease_run(first, cap) as run:
                    for i, v in enumerate(run.views):
                        if v[0] != _pattern(1, (first + i) * ps)[0]:
                            errors.append((tid, first + i))
        except Exception as exc:  # noqa: BLE001
            errors.append((tid, repr(exc)))

    try:
        ts = [threading.Thread(target=worker, args=(t,)) for t in range(3)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
        assert not any(t.is_alive() for t in ts), \
            "concurrent lease_runs deadlocked"
        assert not errors, errors[:5]
    finally:
        uunmap(r)


@pytest.mark.parametrize("slots,shards", [
    (8, 0), (16, 4), (20, 5), (57, 0), (64, 16), (63, 16), (100, 7)])
def test_every_capped_run_fits_its_stripes(slots, shards):
    """Slot free lists are stripe-private, so a run can be granted only if
    no stripe holds more of its pages than it owns slots.  The stripe map
    must guarantee that for every run up to the length cap: with
    ``hash(key) % N`` a 28-page run put 5 pages on a 4-slot stripe and
    ``lease_run`` retried forever (the OOC trainer's livelock)."""
    from repro.core import PagingService

    svc = PagingService(UMapConfig(page_size=4096, buffer_size=slots * 4096,
                                   num_fillers=8, num_evictors=1,
                                   shards=shards))
    try:
        owned = [len(s.free) for s in svc.shards]
        n = len(owned)
        cap = min(svc.config.max_lease_run, svc.buffer.num_slots // 2)
        for rid in range(4):
            for first in range(300):
                need = [0] * n
                for pno in range(first, first + cap):
                    need[svc._shard_index((rid, pno))] += 1
                assert all(k <= o for k, o in zip(need, owned)), \
                    (rid, first, need, owned)
            if n > 1:    # a stride of N pages still spreads over stripes
                hit = {svc._shard_index((rid, 3 + j * n)) for j in range(64)}
                assert len(hit) > n // 2
    finally:
        svc.close()


class _SlowFirstFailingStore(HostArrayStore):
    """Page 0 reads slowly; the first read covering page 3 fails."""

    def __init__(self, data, ps):
        super().__init__(data)
        self.ps = ps
        self.failed = False

    def _hook(self, offset, nbytes):
        if offset == 0:
            time.sleep(0.2)
        if not self.failed and offset <= 3 * self.ps < offset + nbytes:
            self.failed = True
            raise OSError("injected read fault on page 3")

    def read_into(self, offset, buf):
        self._hook(offset, buf.nbytes)
        return super().read_into(offset, buf)

    def read_into_batch(self, offset, bufs):
        self._hook(offset, sum(b.nbytes for b in bufs))
        return super().read_into_batch(offset, bufs)


def test_lease_run_raises_when_a_fill_it_posted_failed():
    """A run waits on every fill it posts.  Page 3's fill fails while the
    grant loop still waits on page 0; the loop would later re-fault page 3
    and succeed, but the caller must see the error all the same (and a
    retry then succeeds)."""
    npages, ps = 16, 4096
    store = _SlowFirstFailingStore(_pattern(npages * ps).copy(), ps)
    r = umap(store, config=UMapConfig(
        page_size=ps, buffer_size=npages * ps, num_fillers=4,
        num_evictors=1, shards=4, max_batch_pages=1))
    try:
        try:
            run = r.lease_run(0, 6)
        except OSError as exc:
            assert "page 3" in str(exc)
        else:
            run.release()
            pytest.fail("lease_run hid the failed fill of page 3")
        assert store.failed
        with r.lease_run(0, 6) as run:
            for i, v in enumerate(run.views):
                assert np.array_equal(v, _pattern(ps, i * ps))
    finally:
        uunmap(r)


def test_abandoned_write_lease_does_not_mark_dirty():
    """Regression (review finding): lease_run's abort path releases
    write-leases whose views were never handed out — they must not dirty
    untouched pages (spurious write-back traffic)."""
    ps = 4096
    store, r = _make_region(ps=ps)
    try:
        before = r.service.table.dirty_count
        ls = r.lease(4, write=True)
        ls.abandon()
        assert r.service.table.dirty_count == before
        e = r.service.table.get((r.region_id, 4))
        assert e.pins == 0 and e.leases == 0
        ls.release()                                # no-op after abandon
        assert r.service.table.dirty_count == before
    finally:
        uunmap(r)


def test_async_checkpointer_store_mode_rejects_oversized_tree(tmp_path):
    """Regression (review finding): an image larger than one double-buffer
    slot must fail fast instead of corrupting the other slot."""
    pytest.importorskip("jax")
    from repro.ckpt.checkpoint import AsyncCheckpointer

    st = HostArrayStore(np.zeros(1 << 12, np.uint8))
    ck = AsyncCheckpointer(tmp_path, store=st)
    try:
        with pytest.raises(ValueError, match="exceeds"):
            ck.save_async(1, {"w": np.zeros(4096, np.float32)})  # 16K > 2K
    finally:
        ck.close()


def test_file_store_batch_io_chunks_past_iov_max(tmp_path):
    """Regression (review finding): pwritev/preadv reject >IOV_MAX iovecs;
    batched store I/O with thousands of buffers must chunk, not EINVAL."""
    nbufs, chunk = 1500, 64                       # > IOV_MAX = 1024
    st = FileStore(str(tmp_path / "big.bin"), size=nbufs * chunk, create=True)
    try:
        payload = _pattern(nbufs * chunk)
        bufs = [payload[i * chunk:(i + 1) * chunk] for i in range(nbufs)]
        assert st.write_from_batch(0, bufs) == nbufs * chunk
        outs = [np.empty(chunk, np.uint8) for _ in range(nbufs)]
        assert st.read_into_batch(0, outs) == nbufs * chunk
        assert np.array_equal(np.concatenate(outs), payload)
    finally:
        st.close()


def test_async_checkpointer_store_mode_double_buffers(tmp_path):
    """Regression (review finding): store-mode saves alternate halves of
    the store and publish the manifest only after the write, so the
    previously published image is never overwritten in place."""
    pytest.importorskip("jax")
    from repro.ckpt.checkpoint import (
        AsyncCheckpointer, restore_tree_from_store)

    st = HostArrayStore(np.zeros(1 << 16, np.uint8))
    ck = AsyncCheckpointer(tmp_path, store=st)
    try:
        tree1 = {"w": np.full(1000, 1.0, np.float32)}
        ck.save_async(1, tree1)
        ck.flush()
        m1 = ck.store_manifest
        ck.save_async(2, {"w": np.full(1000, 2.0, np.float32)})
        ck.flush()
        m2 = ck.store_manifest
        assert m2["step"] == 2 and m2["offset"] != m1["offset"]
        # the step-1 image survives step 2's save intact
        back1 = restore_tree_from_store(st, m1, tree1)
        assert (back1["w"] == 1.0).all()
        back2 = restore_tree_from_store(st, m2, tree1)
        assert (back2["w"] == 2.0).all()
    finally:
        ck.close()


# -------------------------------------------------- lease life-cycle property


def test_lease_lifecycle_property():
    """Property test: any interleaving of leases (read/write, page/run),
    reads, and flushes preserves byte-exactness, pin/lease balance, and
    dirty-exactly-once accounting."""
    pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st_

    NPAGES, PS = 16, 512

    ops = st_.lists(
        st_.tuples(
            st_.sampled_from(["lease_r", "lease_w", "run", "read", "flush"]),
            st_.integers(min_value=0, max_value=NPAGES - 1),
            st_.integers(min_value=1, max_value=4),
        ),
        min_size=1, max_size=30,
    )

    @given(ops)
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def check(script):
        mirror = _pattern(NPAGES * PS).copy()
        store = HostArrayStore(mirror.copy())
        cfg = UMapConfig(page_size=PS, buffer_size=NPAGES * PS,
                         num_fillers=2, num_evictors=1, shards=2)
        r = umap(store, config=cfg)
        try:
            stamp = 0
            for op, pno, n in script:
                if op == "lease_r":
                    with r.lease(pno) as ls:
                        assert np.array_equal(
                            ls.view, mirror[pno * PS:(pno + 1) * PS])
                elif op == "lease_w":
                    before = r.service.table.dirty_count
                    with r.lease(pno, write=True) as ls:
                        was_dirty = r.service.table.get(
                            (r.region_id, pno)).dirty
                        stamp = (stamp + 1) % 251
                        ls.view[:] = stamp
                        mirror[pno * PS:(pno + 1) * PS] = stamp
                    after = r.service.table.dirty_count
                    assert after - before == (0 if was_dirty else 1)
                elif op == "run":
                    n = min(n, NPAGES - pno)
                    with r.lease_run(pno, n) as run:
                        for i, v in enumerate(run.views):
                            assert np.array_equal(
                                v, mirror[(pno + i) * PS:(pno + i + 1) * PS])
                elif op == "read":
                    assert np.array_equal(
                        r.read(pno * PS, PS), mirror[pno * PS:(pno + 1) * PS])
                elif op == "flush":
                    r.flush()
                    chk = np.empty(NPAGES * PS, np.uint8)
                    store.read_into(0, chk)
                    assert np.array_equal(chk, mirror)
            # balance: no pin/lease leaked by any interleaving
            for key in r.service.table.resident_keys():
                e = r.service.table.get(key)
                assert e.pins == 0 and e.leases == 0
        finally:
            uunmap(r)

    check()


# ---------------------------------------------- consumer zero-staging witnesses


def test_weight_pager_region_source_zero_staging():
    jax = pytest.importorskip("jax")
    from repro.serve.weight_pager import (
        LayerWeightPager, RegionLayerSource, pack_layer_arrays)

    rng = np.random.default_rng(0)
    layers = [rng.normal(size=(16, 16)).astype(np.float32) for _ in range(5)]
    ps = 512
    buf, specs = pack_layer_arrays(layers, ps)
    store = HostArrayStore(buf)
    cfg = UMapConfig(page_size=ps, buffer_size=64 * ps, num_fillers=2,
                     num_evictors=1)
    region = umap(store, config=cfg)
    try:
        src = RegionLayerSource(region, specs)
        for i, ref in enumerate(layers):
            assert np.allclose(np.asarray(src[i]), ref), i
        # the witness: every page arrived through a lease, none through a
        # staging copy
        st = region.stats()
        assert st["leases"] == sum(s["npages"] for s in specs)
        assert src.staging_copies == 0
        # and the full pager stack runs over the source
        import jax.numpy as jnp
        pager = LayerWeightPager(src, num_slots=3, readahead=1)
        out = pager.run(jnp.ones((4, 16), jnp.float32),
                        lambda p, x, i: jnp.tanh(x @ p))
        out.block_until_ready()
        pager.close()
    finally:
        uunmap(region)
