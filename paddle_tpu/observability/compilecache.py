"""Persistent compilation cache: where it goes, and who turns it on.

jax can persist compiled executables to disk so a *second process* with
the same program shapes skips XLA entirely — on a chip that turns a
minutes-long cold start into seconds.  The cache directory is part of
the cache key's environment, so a directory that moves never hits; the
placement rule is therefore fixed and decided outside the program:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax's own handling of that variable
  places the cache.  This module sets no directory on that branch.
- unset: :func:`enable_persistent_cache` points jax at ``.jax_cache``
  beside the package (the checkout root, git-ignored) — never a temp
  name, pid or time.

Either way the min-compile-time / min-entry-size floors are zeroed so
even small programs persist, and disk hit/miss traffic is surfaced as
registry counters ``compile.persistent_cache_hits`` /
``compile.persistent_cache_requests`` via jax's monitoring events: a
warm start shows ``hits == requests > 0``.

Call sites: ``jit.to_static``, ``hapi.Model.prepare``, ``ServingEngine``,
the bench entries and ``chip_smoke.py`` — every place the framework is
about to hand jax a program worth caching.  Idempotent.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

__all__ = ["enable_persistent_cache", "persistent_cache_dir",
           "reset_for_tests"]

_lock = threading.Lock()
_state = {"enabled": False, "dir": None, "listener": False}

# jax monitoring event names; the listener ignores anything else
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_REQ = "/jax/compilation_cache/compile_requests_use_cache"

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def persistent_cache_dir() -> Optional[str]:
    """The directory the cache was enabled with (None = not yet)."""
    return _state["dir"]


def _listener(event: str, **kwargs) -> None:
    if event not in (_EV_HIT, _EV_REQ):
        return
    from .registry import get_registry
    reg = get_registry()
    if event == _EV_HIT:
        reg.counter("compile.persistent_cache_hits").inc()
    else:
        reg.counter("compile.persistent_cache_requests").inc()


def enable_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache under the placement
    rule above.  Idempotent; returns the cache dir in effect."""
    with _lock:
        if _state["enabled"]:
            return _state["dir"]
        import jax
        from jax._src import compilation_cache, monitoring
        if os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
            cache_dir = jax.config.jax_compilation_cache_dir
        else:
            cache_dir = _CHECKOUT_CACHE
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # persist everything: the default floors (compile time / entry
        # size) silently skip small programs, which breaks the
        # warm-start contract for smoke-sized workloads
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax latches a cache-used decision on the process's FIRST
        # compile (is_cache_used sets _cache_checked); any eager op
        # before this call — model construction, pt.seed — freezes the
        # cache OFF for the process even though the config above lands.
        # reset_cache() clears the latch; the cache re-initializes
        # lazily from the config on the next compile.
        compilation_cache.reset_cache()
        if not _state["listener"]:
            monitoring.register_event_listener(_listener)
            _state["listener"] = True
        _state["enabled"], _state["dir"] = True, cache_dir
        return cache_dir


def reset_for_tests() -> None:
    """Forget the enabled state and restore jax's floors and latch, so a
    test can enable again.  The directory is left where it was placed.

    A cache that is on for every hapi/engine flow changes what a test
    session measures — a warm second worker once outran its injected
    delay in the doctor straggler drill — so ``tests/conftest.py`` keeps
    the session hermetic with jax's own switches (a per-session
    ``JAX_COMPILATION_CACHE_DIR`` and ``JAX_ENABLE_COMPILATION_CACHE=0``)
    rather than with a rule in this module."""
    with _lock:
        if not _state["enabled"]:
            return
        _state["enabled"], _state["dir"] = False, None
        import jax
        from jax._src import compilation_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
