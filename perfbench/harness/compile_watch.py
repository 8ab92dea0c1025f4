"""Counts what jax compiles and what its persistent cache answers, through
``jax.monitoring`` listeners of the harness's own (the same events that
``paddle_tpu/observability/compilecache.py`` turns into registry counters).
"""
from __future__ import annotations

_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_REQ = "/jax/compilation_cache/compile_requests_use_cache"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """``requests``/``hits``: persistent-cache lookups and hits so far;
    ``compiles``: programs handed to the backend so far (a cache hit still
    passes here: what it counts is "a new program appeared")."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self._installed = False

    def install(self) -> "CompileWatch":
        if self._installed:
            return self
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        self._installed = True
        return self

    def _on_event(self, event: str, **kw) -> None:
        if event == _EV_HIT:
            self.hits += 1
        elif event == _EV_REQ:
            self.requests += 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _EV_BACKEND:
            self.compiles += 1
            self.compile_seconds += float(duration)

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "compiles": self.compiles,
                "compile_seconds": self.compile_seconds}
