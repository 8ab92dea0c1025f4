"""Host spans of the harness's own, around the calls into each layer.

Kept in memory on ``time.perf_counter()``; each span also opens a
``jax.profiler.TraceAnnotation`` (free when no trace is running), so that in
a traced run the same spans sit on the profiler's clock beside the device's
operations and an idle gap can be named by what the host was doing in it.
Spans inside the program are a later ``tracing`` issue (PERF.md section 7).
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

PREFIX = "pb:"          # how the trace reduction knows a harness span


class Spans:
    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []
        self._annotate = None

    def _annotation(self, name: str):
        if self._annotate is None:
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation
        return self._annotate(PREFIX + name)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))
