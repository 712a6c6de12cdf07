"""Host spans of the program, recorded only while a jax profiler session runs.

    from repro.utils.trace import span

    with span("pipeline.subspace"):
        ...

Tracing is on exactly while a profiler session records: between
``jax.profiler.start_trace`` and ``stop_trace``, or while a profiler
server is capturing.  Off, :func:`span` returns one shared no-op context:
it allocates nothing, reads no clock and touches no jax setting, so a span
site costs one ``TraceAnnotation.is_enabled()`` call.  On, a span is a
``jax.profiler.TraceAnnotation`` named ``repro:<name>`` -- in the
profiler's trace, on the clock of the device's ops -- and is also kept
here, in memory, for :func:`records`.

A record is ``(name, start_ns, end_ns, parent, request)`` with the times
on ``time.perf_counter_ns()``.  The outermost span open on a thread starts
a new ``request``; spans nested in it share that id and name their
``parent``.  A span is recorded when it closes, and only if the profiler
still runs then, so a request whose outermost span has no record was cut
by the end of the session.

The store holds one session: the first request opened under a new
``start_trace`` session empties it, and it keeps at most
:data:`MAX_RECORDS`, the newest (a profiler server's captures are not
told apart, so they share the cap).

jax is optional: until it is imported no profiler can run, and this module
does not import it.
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import NamedTuple

PREFIX = "repro:"
# Newest records kept; a session long enough to pass it drops the oldest.
MAX_RECORDS = 1 << 16


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: str | None
    request: int


_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_requests = itertools.count()
_local = threading.local()
_lock = threading.Lock()
_session = None


def _is_enabled_before_jax() -> bool:
    """Whether a profiler session runs: never before jax is imported; once
    it is, ``TraceAnnotation.is_enabled`` answers from then on."""
    global _is_enabled
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation
    _is_enabled = TraceAnnotation.is_enabled
    return _is_enabled()


_is_enabled = _is_enabled_before_jax


def _new_request() -> int:
    """A new request id; the first under a new ``start_trace`` session
    empties the store of the sessions before it."""
    global _session
    from jax._src import profiler
    session = getattr(getattr(profiler, "_profile_state", None),
                      "profile_session", None)
    with _lock:
        if session is not _session:
            _session = session
            _records.clear()
        return next(_requests)


class _Off:
    """The span while tracing is off: shared, and does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


OFF = _Off()


class _Span:
    __slots__ = ("name", "parent", "request", "start_ns", "_annotation",
                 "_stack")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        stack = _local.__dict__.setdefault("stack", [])
        if stack:
            self.parent, self.request = stack[-1].name, stack[-1].request
        else:
            self.parent, self.request = None, _new_request()
        stack.append(self)
        self._stack = stack
        self._annotation = TraceAnnotation(PREFIX + self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._stack.pop()
        if _is_enabled():
            _records.append(Record(self.name, self.start_ns, end_ns,
                                   self.parent, self.request))
        return None


def span(name: str):
    """A host span named ``name``; see the module."""
    if not _is_enabled():
        return OFF
    return _Span(name)


def records() -> list[Record]:
    """The spans recorded in the newest session, in the order they
    closed."""
    return list(_records)


def clear() -> None:
    _records.clear()
