"""Program spans (``repro.telemetry.spans``): nothing is recorded while no
profiler captures; under a capture each span is recorded with its parent,
request id and counts, on every thread, and shows in the profiler's trace;
a new capture starts a new record set; a session holds at most its
bound."""

import contextlib
import threading

import jax
import numpy as np
import pytest

from repro.core import HostArrayStore, UMapConfig, umap, uunmap
from repro.telemetry import spans


@contextlib.contextmanager
def capture(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture
def store(monkeypatch):
    """A store of this test's own, so that no other test's records show."""
    s = spans.SpanStore()
    monkeypatch.setattr(spans, "STORE", s)
    return s


def test_no_record_without_a_capture(store):
    with spans.span("t.off", rid=1, pages=2) as sp:
        sp.add(pages=1)
        assert not sp.recording
    with spans.transfer("h2d", [np.zeros(4, np.float32)]):
        pass
    assert spans.session() == []
    assert spans.dropped() == 0


def test_capture_records_parents_rid_and_counts(store, tmp_path):
    with capture(tmp_path):
        with spans.span("t.outer", rid=7) as outer:
            assert outer.recording
            with spans.span("t.inner", rid=7, pages=2) as inner:
                inner.add(pages=1, bytes=4096)
            with spans.span("t.sibling"):
                pass
    recs = {r.name: r for r in spans.session()}
    assert set(recs) == {"t.outer", "t.inner", "t.sibling"}
    o, i, s = recs["t.outer"], recs["t.inner"], recs["t.sibling"]
    assert o.parent_id is None
    assert i.parent_id == s.parent_id == o.span_id
    assert i.rid == o.rid == 7 and s.rid is None
    assert i.counts == {"pages": 3, "bytes": 4096}
    assert o.start_ns <= i.start_ns <= i.end_ns <= s.start_ns <= o.end_ns
    assert 0 <= i.seconds <= o.seconds


def test_transfer_counts_copies_and_bytes(store, tmp_path):
    arrays = [np.zeros(4, np.float32), np.zeros(3, np.int32)]
    with capture(tmp_path):
        with spans.transfer("d2h", arrays) as sp:
            assert sp.recording
    with spans.transfer("d2h", arrays) as sp:     # not recorded
        assert not sp.recording
    (rec,) = spans.session()
    assert rec.name == "umap.transfer.d2h"
    assert rec.counts == {"copies": 2, "bytes": 28}


def test_spans_of_every_thread_are_recorded(store, tmp_path):
    def work():
        with spans.span("t.thread", rid=3):
            pass

    with capture(tmp_path):
        with spans.span("t.main"):
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
    assert not t.is_alive()
    recs = {r.name: r for r in spans.session()}
    assert recs["t.thread"].parent_id is None      # stacks are per thread
    assert recs["t.thread"].thread != recs["t.main"].thread
    assert recs["t.thread"].rid == 3


def test_pager_threads_record_fill_wait_and_writeback(store, tmp_path):
    ps, npages = 4096, 16
    data = np.arange(npages * ps, dtype=np.uint8)
    cfg = UMapConfig(page_size=ps, buffer_size=4 * ps, num_fillers=2,
                     num_evictors=1)
    with capture(tmp_path):
        region = umap(HostArrayStore(data.copy()), config=cfg)
        try:
            region.write(0, np.full(npages * ps, 9, np.uint8))
            assert (region.read(0, npages * ps) == 9).all()
        finally:
            uunmap(region)
    recs = spans.session()
    main = threading.get_ident()
    fills = [r for r in recs if r.name == "umap.pager.fill"]
    assert fills and all(r.thread != main for r in fills)
    assert sum(r.counts["bytes"] for r in fills) \
        == ps * sum(r.counts["pages"] for r in fills)
    waits = [r for r in recs if r.name == "umap.pager.fault_wait"]
    assert waits and all(r.thread == main for r in waits)
    backs = [r for r in recs if r.name == "umap.pager.writeback"]
    assert sum(r.counts["pages"] for r in backs) >= npages - 4


def test_a_new_capture_starts_a_new_record_set(store, tmp_path):
    for name in ("t.first", "t.second"):
        with capture(tmp_path / name):
            with spans.span(name):
                pass
        with spans.span("t.between"):           # work between captures
            pass
    assert [r.name for r in spans.session()] == ["t.second"]
    with capture(tmp_path / "third"):           # a read came before it
        with spans.span("t.third"):
            pass
        assert [r.name for r in spans.session()] == ["t.third"]
    assert [r.name for r in spans.session()] == ["t.third"]


def test_a_session_holds_at_most_its_bound(monkeypatch, tmp_path):
    monkeypatch.setattr(spans, "STORE", spans.SpanStore(capacity=3))
    with capture(tmp_path):
        for i in range(5):
            with spans.span("t.bounded", n=i):
                pass
    assert [r.counts["n"] for r in spans.session()] == [0, 1, 2]
    assert spans.dropped() == 2


def test_spans_show_on_the_traces_host_plane(store, tmp_path):
    with capture(tmp_path):
        with spans.span("t.in_trace"):
            pass
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for p in pd.planes if p.name == "/host:CPU"
             for ln in p.lines for ev in ln.events}
    assert "t.in_trace" in names
