"""Typed span tracing (ISSUE 3; repaired for the serving loop, ISSUE 26;
the ring holds a whole serving run and takes spans that were never a ``with``
block, ISSUE 37).

``with span("data_load"): ...`` / ``with span("step"): ...`` nest on a
per-thread stack; a nested span's identity is its *path* ("step/dispatch"),
so the same leaf name under different parents stays distinguishable and
the path names the span that caused it.  Keyword attributes
(``span("engine.step", step=7)``, or ``sp.set(kind="decode")`` once they
are known) stay with the record: spans of one step share its ``step``.
Every span feeds three consumers at once:

- the existing :mod:`paddle_tpu.profiler` host-annotation machinery
  (``RecordEvent`` → jax TraceAnnotation named by the path + the flat
  host table), so spans land inside the XPlane device timeline exactly
  like hand-written annotations;
- an aggregated **span tree** (path → count / total ms / self ms, where
  self excludes child spans) — surfaced by ``Profiler.summary()``;
- a bounded in-memory buffer of completed spans on
  ``time.perf_counter()`` — the clock a benchmark harness stamps its own
  spans with, so the two join — read by :func:`spans_between` and
  exportable as a chrome://tracing JSON via :func:`export_chrome_trace`.

All three are process-wide and thread-safe; the buffer holds the newest
``BUFFER_SPANS`` spans, and :func:`dropped` says when a reader's window is
no longer whole.  A span whose two ends were stamped elsewhere (a request's
wait in a queue: from ``submit()`` to the launch that took it) goes into the
tree and the buffer through :func:`record`.  ``perf_counter()`` is the one
clock of everything here; ``_WALL_OFFSET`` is the only way from it to wall
time.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..utils import fsio

__all__ = ["span", "record", "span_tree_totals", "spans_between", "dropped",
           "export_chrome_trace", "reset_tracing", "trace_events"]

# a serving run whole: a chat engine makes 215 calls a second of 11 spans
# each, and a benchmark run reads back over 80 s of them (189,200)
BUFFER_SPANS = 1 << 18
# perf_counter() + this = time.time(): taken once, so exported wall times
# keep the spacing the monotonic clock measured
_WALL_OFFSET = time.time() - time.perf_counter()

_RecordEvent = None                # paddle_tpu.profiler's, on first use
_tls = threading.local()
_lock = threading.Lock()
# path -> [count, total_s, self_s]
_tree: Dict[str, list] = {}
# (path, t0, dur, tid, names, *values) in order of completion, t0 on
# perf_counter(): the attributes flat, their names one shared tuple a set
# of names and the path one shared string, so that a record of the serving
# loop's children (two attributes) is the tuple and its two floats, 144
# bytes (195 a record with the roots and the integers, a full ring 51 MB)
_buffer: deque = deque(maxlen=BUFFER_SPANS)
_dropped = [0, float("-inf")]      # evicted spans, end of the newest one
_names: Dict[tuple, tuple] = {}


def _keep(path: str, t0: float, dur: float, self_s: float,
          attrs: Dict[str, Any]) -> None:
    """One completed span into the tree and the buffer."""
    names = tuple(attrs)
    tid = threading.get_ident()
    with _lock:
        row = _tree.get(path)
        if row is None:
            _tree[path] = [1, dur, self_s]
        else:
            row[0] += 1
            row[1] += dur
            row[2] += self_s
        if len(_buffer) == _buffer.maxlen:
            old = _buffer[0]
            _dropped[0] += 1
            _dropped[1] = max(_dropped[1], old[1] + old[2])
        _buffer.append((path, t0, dur, tid, _names.setdefault(names, names),
                        *attrs.values()))


def _attrs(rec: tuple) -> Dict[str, Any]:
    return dict(zip(rec[4], rec[5:]))


class span:
    """Nesting context manager timing one region of host code.

    >>> with span("step", step=3) as sp:
    ...     with span("dispatch"):
    ...         ...        # recorded as "step/dispatch"
    ...     sp.set(kind="decode")

    ``elapsed`` (seconds) is available after exit — callers that need the
    number (hapi's step breakdown) read it instead of re-timing — and so
    are ``start`` (from entry on) and ``end``, the span's two stamps on
    ``perf_counter()``: the serving engine's unit ledger is made of them.
    """

    __slots__ = ("name", "path", "elapsed", "attrs", "start", "end",
                 "_child", "_event")

    def __init__(self, name: str, **attrs: Any):
        self.name = str(name)
        self.path = self.name
        self.elapsed = 0.0
        self.attrs = attrs
        self._child = 0.0

    def set(self, **attrs: Any) -> None:
        """Attributes learned while the span is open."""
        self.attrs.update(attrs)

    def __enter__(self) -> "span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            self.path = sys.intern(stack[-1].path + "/" + self.name)
        stack.append(self)
        # feed the profiler's host-annotation machinery (TraceAnnotation
        # into the device timeline + the flat host table)
        global _RecordEvent
        if _RecordEvent is None:
            from ..profiler import RecordEvent as _RecordEvent
        self._event = _RecordEvent(self.path)
        self._event.begin()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._event.end()
        self.elapsed = dt = self.end - self.start
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            # an asynchronous exception (the watchdog's) cut a child off
            # between its push and its ``with``: it goes with its parent
            del stack[stack.index(self):]
        if stack:
            stack[-1]._child += dt
        _keep(self.path, self.start, dt, max(0.0, dt - self._child),
              self.attrs)


def record(path: str, start: float, end: float, **attrs: Any) -> None:
    """A completed span that was never a ``with`` block: ``path`` whole
    (``"engine.request/queue"``), its two ends on ``perf_counter()``.  It
    counts in the tree under its path (all of it self time; no parent's
    self time shrinks) and sits in the buffer and the chrome export like
    any span, on the thread that recorded it."""
    dur = max(0.0, end - start)
    _keep(sys.intern(path), start, dur, dur, attrs)


def span_tree_totals(reset: bool = False) -> Dict[str, Dict[str, float]]:
    """Aggregated span stats: path → {count, total_ms, self_ms} (self
    excludes time spent inside child spans)."""
    with _lock:
        out = {path: {"count": row[0], "total_ms": row[1] * 1e3,
                      "self_ms": row[2] * 1e3}
               for path, row in sorted(_tree.items())}
        if reset:
            _tree.clear()
    return out


def spans_between(t0: float, t1: float
                  ) -> List[Tuple[str, float, float, Dict[str, Any]]]:
    """The buffered spans that overlap ``[t0, t1]`` (``perf_counter()``
    seconds), oldest first, as ``(path, start, end, attrs)``.  Ask
    :func:`dropped` whether the buffer still holds all of them."""
    with _lock:
        items = list(_buffer)
    return [(r[0], r[1], r[1] + r[2], _attrs(r)) for r in items
            if r[1] < t1 and r[1] + r[2] > t0]


def dropped(since: float = float("-inf")) -> int:
    """How many spans the bounded buffer has let go, if any of them ended
    after ``since`` (``perf_counter()`` seconds); else 0.  Nonzero means a
    window that opens at ``since`` is no longer whole."""
    with _lock:
        return _dropped[0] if _dropped[1] > since else 0


def trace_events() -> list:
    """The buffered completed spans as chrome trace events (µs units,
    wall clock)."""
    with _lock:
        items = list(_buffer)
    pid = os.getpid()
    return [{"name": r[0], "ph": "X", "ts": (r[1] + _WALL_OFFSET) * 1e6,
             "dur": r[2] * 1e6, "pid": pid, "tid": r[3],
             **({"args": _attrs(r)} if r[4] else {})}
            for r in items]


def export_chrome_trace(path: str, reset: bool = False) -> int:
    """Write the buffered spans as a chrome://tracing / Perfetto JSON;
    returns the number of events written."""
    events = trace_events()
    payload = json.dumps({"traceEvents": events,
                          "displayTimeUnit": "ms"}).encode("utf-8")
    fsio.atomic_write_bytes(path, payload)
    if reset:
        with _lock:
            _buffer.clear()
    return len(events)


def reset_tracing() -> None:
    """Drop the span tree and the trace buffer (tests)."""
    with _lock:
        _tree.clear()
        _buffer.clear()
        _dropped[:] = [0, float("-inf")]


def current_span() -> Optional[Any]:
    """The innermost open span on this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None
