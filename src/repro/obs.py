"""Spans of the program's own host work, in memory and in the profiler's trace.

``span(name, **attrs)`` times a block of host work. It does two things:

  * it enters ``jax.profiler.TraceAnnotation(name)`` (the name only, so an
    idle profiler costs one flat call), so that in a profiled run the span
    sits in the ``.xplane.pb`` on the device trace's clock, beside the
    device ops: the operator's view in TensorBoard or Perfetto;
  * it appends one ``Span`` to a bounded in-memory ring: name, thread,
    start and end on ``time.perf_counter_ns``, the enclosing span of the
    same thread (``parent``) and ``attrs``.

``record`` stores a span whose start and end happen on different threads
(a request: submitted on one, resolved on another). ``spans()`` returns
the ring oldest first, in the order the spans ended; once the ring is
full the oldest records go, so a reader checks from the first record
whether the ring still covers the interval it reads. Every garbage
collection is recorded as a ``host.gc`` span with its ``generation``.

The recorder is always on and there is one per process, like the
profiler it writes beside. Appends and the copy in ``spans`` are single
C-level deque operations, atomic under the interpreter lock, so the
record takes no lock (a lock here could be re-entered by the collector's
callback on the thread that holds it).
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 65_536  # records kept; about 6,000 batches of the served path


class Span(NamedTuple):
    id: int
    name: str
    thread: str
    t0: int  # time.perf_counter_ns() at the start
    t1: int  # ... and at the end
    parent: Optional[int]  # id of the enclosing span on the same thread
    attrs: dict


_ring: "collections.deque[Span]" = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()


def new_id() -> int:
    """A process-unique id, for joining spans of one batch or one front."""
    return next(_ids)


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _top() -> Optional[int]:
    st = _stack()
    return st[-1] if st else None


class span:
    """``with span("db.query", bucket=8) as sp: ...``; ``sp.attrs`` may
    take more attributes inside the block."""

    __slots__ = ("name", "attrs", "_id", "_parent", "_t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        st = _stack()
        self._parent = st[-1] if st else None
        self._id = next(_ids)
        st.append(self._id)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        _stack().pop()
        self._ann.__exit__(*exc)
        _ring.append(Span(self._id, self.name,
                          threading.current_thread().name, self._t0, t1,
                          self._parent, self.attrs))


def record(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Store a span timed elsewhere, e.g. one that crosses threads. Its
    parent is the innermost span open on the calling thread."""
    _ring.append(Span(next(_ids), name, threading.current_thread().name,
                      int(t0_ns), int(t1_ns), _top(), attrs))


def spans() -> list:
    """The ring's records, oldest first (by end)."""
    while True:
        try:
            return list(_ring)
        except RuntimeError:  # appended to mid-copy: copy again
            continue


class _GCSpans:
    """``gc.callbacks`` hook: each collection becomes a ``host.gc`` span."""

    def __init__(self):
        self.t0, self.ann = 0, None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.ann = TraceAnnotation("host.gc")
            self.ann.__enter__()
            self.t0 = time.perf_counter_ns()
        elif self.ann is not None:
            t1 = time.perf_counter_ns()
            self.ann.__exit__(None, None, None)
            self.ann = None
            record("host.gc", self.t0, t1, generation=info["generation"])


gc.callbacks.append(_GCSpans())
