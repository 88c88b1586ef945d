"""Spans and counters of the serving path, recorded while a profiler
session is active.

A span marks one piece of the serving path's work (an engine cycle, the
drain's readback, a refill, ...).  Each span

- writes a ``jax.profiler.TraceAnnotation`` of the same name, so it lands
  in the profiler's trace on the profiler's clock, beside the device's
  operations; its counts, those set while it is open included, reach the
  trace event as metadata;
- appends a :class:`Span` to a bounded in-memory buffer, both ends read
  from ``time.perf_counter_ns``, with the recording thread, the enclosing
  span's name on that thread, and its counts.

Recording is on exactly while a profiler session is (``jax.profiler
.start_trace`` .. ``stop_trace``, or ``jax.profiler.trace``); otherwise a
span site costs one check and records nothing.  The profiler session is
process-wide, and so is the record of it: one :data:`RECORDER` per
process, read through :func:`snapshot`.

Usage::

    with spans.span("serve.dispatch", lanes=L) as s:
        ...
        if s:                       # None while recording is off
            s.set(lanes_busy=n)     # a count known only at the end
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# the one place the profiler's on-switch (``TraceMe.is_enabled``, which
# ``TraceAnnotation`` inherits) is read: if a JAX upgrade moves it, the
# import of this module fails instead of recording silently stopping
_recording = TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()


class Span(NamedTuple):
    """One recorded span: ``start_ns``/``end_ns`` from
    ``time.perf_counter_ns``; ``thread`` the recording thread's ident;
    ``parent`` the name of the span open around it on that thread."""
    name: str
    start_ns: int
    end_ns: int
    thread: int
    parent: Optional[str]
    attrs: Dict[str, Any]


class _Open:
    """A span while it is open: the handle ``span(...)`` yields."""

    __slots__ = ("_rec", "_stack", "name", "attrs", "parent", "_ann",
                 "start_ns")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self._rec, self.name, self.attrs = rec, name, attrs

    def set(self, **attrs) -> None:
        """Set counts on the open span, in its record and its trace event."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "_Open":
        self._stack = self._rec._stack()
        self.parent = self._stack[-1].name if self._stack else None
        self._stack.append(self)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._stack.pop()
        self._ann.__exit__(*exc)
        self._rec._buf.append(Span(self.name, self.start_ns, end,
                                   threading.get_ident(), self.parent,
                                   self.attrs))


class Recorder:
    """A bounded buffer of :class:`Span` records (the oldest drop first).
    Appends come from the submitting threads and the runner thread; a
    ``deque`` append is atomic, and the stack of open spans is per thread.
    """

    def __init__(self, maxlen: int = 1 << 16):
        self._buf: deque = deque(maxlen=maxlen)
        self._local = threading.local()

    def _stack(self) -> List[_Open]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs):
        """Context manager for one span; yields its handle while recording
        is on, ``None`` otherwise."""
        if not _recording():
            return _OFF
        return _Open(self, name, attrs)

    def record(self, name: str, start_ns: int, end_ns: int,
               **attrs) -> None:
        """Record a span after the fact, e.g. one that began on another
        thread (ends from ``time.perf_counter_ns``); it has no parent.  A
        profiler event cannot cross threads, so the trace gets a marker
        where it is recorded, carrying the span's length (``dur_ns``)."""
        if not _recording():
            return
        with TraceAnnotation(name, dur_ns=end_ns - start_ns, **attrs):
            pass
        self._buf.append(Span(name, start_ns, end_ns, threading.get_ident(),
                              None, attrs))

    def snapshot(self) -> List[Span]:
        """The records so far, oldest first; the buffer is left as is."""
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()


#: the process's recorder (the profiler session it follows is process-wide)
RECORDER = Recorder()
span = RECORDER.span
record = RECORDER.record
snapshot = RECORDER.snapshot
clear = RECORDER.clear
