"""Spans of the serving path: one log for the whole process.

Every stage boundary of a :class:`~repro.service.PopService` step opens a
:func:`span`.  A span does two things:

* it opens a ``jax.profiler.TraceAnnotation`` under the same name, so
  that under ``jax.profiler.trace`` the stage lands in the profiler's
  host plane, on the same clock as the device's ``XLA Ops``;
* on exit it appends one :class:`Span` to a bounded in-memory ring: name,
  span id, parent id (the span open on the same thread when it began),
  request id (inherited from the parent unless given), start and end on
  ``time.perf_counter_ns``, the thread's CPU time spent inside
  (``time.thread_time_ns``, for the spans opened with ``cpu=True``) and
  the attributes.

:func:`record` appends an interval that began on one thread and ended on
another (a request waiting for a pool worker, a ticket waiting in the
dispatcher's queue); it reaches the ring only, since a profiler
annotation cannot cross threads.  :func:`spans` returns a copy of the
ring.  The ring is always on; with the profiler off a span costs a few
microseconds, and reading the thread's CPU clock may cost several more
(a system call on some kernels; 10 ms resolution on a TPU v5e host), so
only the stages of a step that do host work read it.  docs/SERVING.md
lists the span names.

Spans belong on the host, around eager work: inside a traced or jitted
function a span would time the tracing, not the run.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

__all__ = ["RING_SIZE", "Span", "current", "new_request", "record", "span",
           "spans"]

# records the ring keeps: a benchmark window of ~750 requests writes about
# 15 per request, with room for the set-up steps before it
RING_SIZE = 1 << 15

_ring: "collections.deque[Span]" = collections.deque(maxlen=RING_SIZE)
_ring_lock = threading.Lock()
_appended = 0
_span_ids = itertools.count(1)
_request_ids = itertools.count(1)
_local = threading.local()


class Span(NamedTuple):
    """One finished span.  ``cpu_ns`` is None for a span opened without
    ``cpu=True`` and for a :func:`record`ed interval."""

    name: str
    id: int
    parent: Optional[int]
    rid: Optional[int]
    t0_ns: int
    t1_ns: int
    cpu_ns: Optional[int]
    attrs: dict

    @property
    def dur_ns(self) -> int:
        return self.t1_ns - self.t0_ns


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _append(s: Span) -> None:
    global _appended
    with _ring_lock:
        _ring.append(s)
        _appended += 1


def new_request() -> int:
    """A fresh request id: every span of one step carries it."""
    return next(_request_ids)


def current() -> tuple:
    """``(span id, request id)`` of the innermost span open on this thread,
    ``(None, None)`` outside any."""
    st = _stack()
    return st[-1] if st else (None, None)


class span:
    """Context manager timing one stage: ``with span("pop.build"): ...``.

    ``rid`` names the request (default: the enclosing span's);
    ``start_ns`` backdates the recorded start to an earlier
    ``perf_counter_ns`` reading taken on another thread (a request's
    submit time); the profiler annotation still opens at entry.
    ``cpu=True`` also records the thread's CPU time inside.
    ``set(**attrs)`` adds attributes known only inside the span; they
    reach the ring, not the profiler annotation.  After exit ``dur_ns``
    holds the recorded duration."""

    __slots__ = ("name", "rid", "attrs", "id", "parent", "dur_ns",
                 "_start", "_t0", "_c0", "_ann")

    def __init__(self, name: str, *, rid: Optional[int] = None,
                 start_ns: Optional[int] = None, cpu: bool = False,
                 **attrs):
        self.name = name
        self.rid = rid
        self.attrs = attrs
        self._start = start_ns
        self._c0 = 0 if cpu else None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "span":
        st = _stack()
        self.parent, outer_rid = st[-1] if st else (None, None)
        if self.rid is None:
            self.rid = outer_rid
        self.id = next(_span_ids)
        st.append((self.id, self.rid))
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        if self._c0 is not None:
            self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        cpu = (None if self._c0 is None
               else time.thread_time_ns() - self._c0)
        self._ann.__exit__(*exc)
        _stack().pop()
        t0 = self._t0 if self._start is None else self._start
        self.dur_ns = t1 - t0
        _append(Span(self.name, self.id, self.parent, self.rid, t0, t1, cpu,
                     self.attrs))
        return False


def record(name: str, t0_ns: int, t1_ns: int, *,
           rid: Optional[int] = None, parent: Optional[int] = None,
           **attrs) -> None:
    """Append an interval timed elsewhere, on ``perf_counter_ns``.
    ``rid`` and ``parent`` default to the span open on this thread."""
    if rid is None and parent is None:
        parent, rid = current()
    _append(Span(name, next(_span_ids), parent, rid, int(t0_ns), int(t1_ns),
                 None, attrs))


def spans() -> tuple:
    """``(records, wrapped)``: a copy of the ring, oldest first (in the
    order the spans ended), and whether older records have been dropped
    to make room."""
    with _ring_lock:
        return list(_ring), _appended > RING_SIZE
