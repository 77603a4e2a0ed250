"""Program spans on the profiler's clock (DESIGN.md §15.5).

``span(name, rid=None, **counts)`` marks one piece of work at a layer
boundary.  Every span is a ``jax.profiler.TraceAnnotation``, so it lands
on the host plane of the profiler's trace beside the device's operations
and on the same clock: an idle gap of the device is named by the span the
host was in.

While a profiler captures (``TraceAnnotation.is_enabled()``, true between
``jax.profiler.start_trace`` and ``stop_trace``) a span also appends one
:class:`Record` to an in-memory store: its name, start and end in
nanoseconds, its id, the id of the span it ran inside on the same thread,
the request id ``rid`` and its integer ``counts``.  A span or a read that
finds the profiler off closes the store's session, and the next record
starts a new one, so a capture with any span or read before it has a
session of its own; :func:`session` returns the latest session's records.
With no profiler capturing a span makes one check and records nothing,
and in a process that never imported JAX it makes none.

Each session holds at most ``capacity`` records; a span that finds its
session full is counted in :func:`dropped` and kept only in the trace.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

CAPACITY = 1 << 20


class Record(NamedTuple):
    name: str
    start_ns: int            # the profiler's clock: wall-clock nanoseconds
    end_ns: int
    span_id: int
    parent_id: Optional[int]  # the enclosing span on the same thread
    rid: Optional[int]        # the request the work belongs to
    counts: Dict[str, int]
    thread: int               # threading.get_ident() of the recording thread

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class _Session:
    __slots__ = ("records", "dropped")

    def __init__(self):
        self.records: List[Record] = []
        self.dropped = 0


class SpanStore:
    """Records of the latest profiler capture, bounded and thread-safe."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._session: Optional[_Session] = None
        self._closed = True       # a span or a reader saw the profiler off

    def current(self) -> _Session:
        """The session of the running capture: a new one when a span or a
        reader has seen the profiler off since the last record."""
        with self._lock:
            if self._closed:
                self._session = _Session()
                self._closed = False
            return self._session

    def close(self) -> None:
        self._closed = True

    def add(self, session: _Session, record: Record) -> None:
        with self._lock:
            if len(session.records) < self.capacity:
                session.records.append(record)
            else:
                session.dropped += 1

    def records(self) -> List[Record]:
        s = self._session
        return list(s.records) if s is not None else []

    def dropped(self) -> int:
        s = self._session
        return s.dropped if s is not None else 0


STORE = SpanStore()

_ids = itertools.count(1)
_local = threading.local()
_annotation = None           # jax.profiler.TraceAnnotation, once JAX is loaded


def _capturing():
    """The ``TraceAnnotation`` class while a profiler captures, else None.

    A process that has not imported JAX runs no profiler, so the pager's
    spans do not pull JAX in."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation if _annotation.is_enabled() else None


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class _Off:
    """The span handed out while no profiler captures: does nothing."""

    recording = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **counts) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("name", "rid", "counts", "_session", "_annotation",
                 "_start", "span_id", "parent_id")

    recording = True

    def __init__(self, annotation, name: str, rid, counts: dict):
        self.name = name
        self.rid = rid
        self.counts = counts
        self._session = STORE.current()
        self._annotation = annotation(name)

    def __enter__(self):
        stack = _stack()
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(_ids)
        stack.append(self.span_id)
        self._annotation.__enter__()
        self._start = time.time_ns()
        return self

    def __exit__(self, *exc):
        end = time.time_ns()
        self._annotation.__exit__(*exc)
        _stack().pop()
        STORE.add(self._session, Record(
            self.name, self._start, end, self.span_id, self.parent_id,
            self.rid, self.counts, threading.get_ident()))
        return False

    def add(self, **counts) -> None:
        """Add to the span's counts (for what is known only inside it)."""
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + int(v)


def span(name: str, rid: Optional[int] = None, **counts):
    """A context manager marking ``name`` in the profiler's trace and,
    while a profiler captures, recording it with ``rid`` and ``counts``.
    Its ``recording`` attribute says which."""
    annotation = _capturing()
    if annotation is None:
        STORE.close()
        return _OFF
    return _Span(annotation, name, rid, {k: int(v) for k, v in counts.items()})


def transfer(direction: str, arrays: Sequence):
    """A ``umap.transfer.<direction>`` span (``h2d`` or ``d2h``) around the
    copies of ``arrays`` between host and device; while it records, it
    counts the copies and their bytes."""
    sp = span(f"umap.transfer.{direction}")
    if sp.recording:
        sp.add(copies=len(arrays), bytes=sum(int(a.nbytes) for a in arrays))
    return sp


def session() -> List[Record]:
    """Records of the latest profiler capture (of the one running, while it
    runs); empty when no span has recorded since the process started."""
    if _capturing() is None:
        STORE.close()
    return STORE.records()


def dropped() -> int:
    """Spans of the latest capture left out because its session was full."""
    return STORE.dropped()
