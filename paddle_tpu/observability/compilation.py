"""Compile/retrace tracking (ISSUE 4).

``jax.jit`` recompiles whenever an argument's *signature* — pytree
structure, leaf shapes/dtypes, or a static value — differs from every
trace it has cached.  On a TPU pod a retrace costs seconds to minutes of
XLA time, so a data pipeline that leaks one ragged batch shape per step
("retrace storm") silently turns an MFU-45% run into a compile farm.
The PR 3 telemetry spine records *how long* a step took; this module
records *why* it recompiled.

:func:`track_jit` wraps an already-jitted callable with a signature
cache that mirrors jax's own cache key (structure + shape/dtype of array
leaves + repr of static leaves).  Every call classifies as a cache hit
or miss; misses beyond the first are **retraces**, and each retrace is
diffed against the previous trace's signature to name *which argument*
changed and how (``data[1]: f32[2,8] -> f32[2,12]``).  When
``storm_threshold`` retraces land within a ``storm_window``-call window,
a ``compile.retrace_storm`` record is emitted naming the most frequent
culprit argument — the one line a run doctor needs.

Instruments (per wrapped function ``<name>``):

- counter   ``compile.count[fn=<name>]``      — traces (first + retraces)
- counter   ``compile.cache_hit[fn=<name>]``  — calls served from cache
- counter   ``compile.retraces[fn=<name>]``   — misses beyond the first
- counter   ``compile.storms[fn=<name>]``     — storm detections
- histogram ``compile.wall_ms[fn=<name>]``    — miss-call wall time
  (trace + XLA compile dominate it; the honest proxy available on every
  backend without PJRT compile callbacks)

Event records: ``compile`` (one per miss, with ``changed`` naming the
diffed arguments) and ``compile.retrace_storm``.

What a hit costs (ISSUE 33): nothing that grows with the arguments.  A
jitted callable says itself whether a call traced (its cache grew), so
the wrapper builds signatures only on the calls that did, and on a
name's first; ``stats(name)["walks"]`` counts them beside ``"calls"``.
A retrace is therefore diffed against the previous TRACE of that name.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import roofline

__all__ = ["arg_signature", "diff_signatures", "CompileTracker",
           "track_jit", "get_tracker", "reset_tracker"]


def _describe_leaf(x: Any) -> str:
    """Shape/dtype for array-likes (``f32[4,6]``), bounded repr for
    static leaves — mirrors what jax's cache key sees."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    r = repr(x)
    return r if len(r) <= 64 else r[:61] + "..."


def arg_signature(arg: Any) -> Tuple[str, Tuple[str, ...]]:
    """One argument's trace signature: (pytree structure, leaf descs).

    Two calls with equal signatures land on the same jax trace; a
    differing signature forces a retrace.  Scalars/None/strings are
    pytree leaves (or empty trees) and show up in the repr half.
    """
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(arg)
    return (str(treedef), tuple(_describe_leaf(x) for x in leaves))


def diff_signatures(prev: Sequence[Tuple[str, Tuple[str, ...]]],
                    cur: Sequence[Tuple[str, Tuple[str, ...]]],
                    names: Sequence[str]) -> List[Dict[str, str]]:
    """Name every argument whose signature changed between two traces.

    Returns ``[{"arg": name, "detail": "f32[2,8] -> f32[2,12]"}, ...]``;
    an argument whose pytree *structure* changed reports
    ``"structure changed"`` plus the structural reprs.
    """
    changed: List[Dict[str, str]] = []
    n = max(len(prev), len(cur))
    for i in range(n):
        name = names[i] if i < len(names) else f"arg{i}"
        if i >= len(prev) or i >= len(cur):
            changed.append({"arg": name, "detail": "added/removed"})
            continue
        (ptree, pleaves), (ctree, cleaves) = prev[i], cur[i]
        if ptree != ctree:
            changed.append({"arg": name, "detail": "structure changed"})
            continue
        if pleaves == cleaves:
            continue
        for j, (a, b) in enumerate(zip(pleaves, cleaves)):
            if a != b:
                detail = f"{a} -> {b}"
                if len(pleaves) > 1:
                    detail = f"leaf {j}: {detail}"
                changed.append({"arg": name, "detail": detail})
                break  # one leaf names the argument; don't spam
    return changed


class _FuncState:
    __slots__ = ("names", "seen", "last_sig", "traces", "retraces",
                 "storms", "recent", "calls", "walks")

    def __init__(self, names: Sequence[str]):
        self.names = list(names)
        self.seen: set = set()
        self.last_sig: Optional[List[Tuple[str, Tuple[str, ...]]]] = None
        self.traces = 0
        self.retraces = 0
        self.storms = 0
        self.calls = 0
        self.walks = 0
        # (call index, changed-arg names) of recent retraces
        self.recent: deque = deque(maxlen=64)


class CompileTracker:
    """Process-wide compile/retrace accountant.

    ``registry`` defaults to the global metrics registry at call time, so
    records land on the run's JSONL timeline like every other emitter.
    ``storm_threshold`` retraces of one function within the last
    ``storm_window`` calls flag a storm (and re-arm: the next storm needs
    a fresh ``storm_threshold`` retraces).
    """

    def __init__(self, registry=None, storm_threshold: int = 3,
                 storm_window: int = 16, max_signatures: int = 4096):
        self._registry = registry
        self.storm_threshold = int(storm_threshold)
        self.storm_window = int(storm_window)
        self.max_signatures = int(max_signatures)
        self._lock = threading.Lock()
        self._funcs: Dict[str, _FuncState] = {}

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from .registry import get_registry
        return get_registry()

    # -- introspection -----------------------------------------------------
    def stats(self, name: str) -> Dict[str, int]:
        with self._lock:
            st = self._funcs.get(name)
            if st is None:
                return {"calls": 0, "walks": 0, "traces": 0, "retraces": 0,
                        "storms": 0}
            return {"calls": st.calls, "walks": st.walks,
                    "traces": st.traces, "retraces": st.retraces,
                    "storms": st.storms}

    def functions(self) -> List[str]:
        with self._lock:
            return sorted(self._funcs)

    def reset(self) -> None:
        with self._lock:
            self._funcs.clear()

    # -- the observation path ----------------------------------------------
    def note_hit(self, name: str) -> bool:
        """Book a call that the jitted callable says it served from its
        cache: no signature is built.  False, and nothing booked, for a
        name this tracker has not walked yet (its first call, or the
        first after :meth:`reset`): the caller walks that one."""
        with self._lock:
            st = self._funcs.get(name)
            if st is None or st.last_sig is None:
                return False
            st.calls += 1
        self._reg().counter(f"compile.cache_hit[fn={name}]").inc()
        return True

    def observe(self, name: str, args: Sequence[Any],
                arg_names: Optional[Sequence[str]] = None,
                wall_ms: Optional[float] = None) -> Optional[dict]:
        """Classify one call by its arguments' signatures; returns the
        emitted ``compile`` record on a miss, None on a hit.  For code
        that times its own compiles."""
        return self.observe_signatures([arg_signature(a) for a in args],
                                       name=name, arg_names=arg_names,
                                       wall_ms=wall_ms)

    def observe_signatures(self, sigs: List[Tuple[str, Tuple[str, ...]]],
                           name: str,
                           arg_names: Optional[Sequence[str]] = None,
                           wall_ms: Optional[float] = None
                           ) -> Optional[dict]:
        """Like :meth:`observe` but with pre-computed signatures (a
        walk: ``stats(name)["walks"]`` counts these)."""
        key = hash(tuple(sigs))
        names = list(arg_names or [])
        while len(names) < len(sigs):
            names.append(f"arg{len(names)}")
        reg = self._reg()
        with self._lock:
            st = self._funcs.get(name)
            if st is None:
                st = self._funcs[name] = _FuncState(names)
            st.calls += 1
            st.walks += 1
            if key in st.seen:
                hit = True
            else:
                hit = False
                if len(st.seen) < self.max_signatures:
                    st.seen.add(key)
                st.traces += 1
                if st.last_sig is not None:
                    st.retraces += 1
            prev, call_idx = st.last_sig, st.calls
            st.last_sig = sigs
        if hit:
            reg.counter(f"compile.cache_hit[fn={name}]").inc()
            return None
        reg.counter(f"compile.count[fn={name}]").inc()
        if wall_ms is not None:
            reg.histogram(f"compile.wall_ms[fn={name}]").observe(wall_ms)
        changed: List[Dict[str, str]] = []
        retrace = prev is not None
        if retrace:
            changed = diff_signatures(prev, sigs, names)
            reg.counter(f"compile.retraces[fn={name}]").inc()
        record = {"function": name, "trace": True, "retrace": retrace,
                  "changed": changed, "wall_ms": wall_ms,
                  "nargs": len(sigs)}
        reg.emit("compile", **record)
        if retrace:
            self._maybe_storm(name, call_idx, changed, reg)
        return record

    def _maybe_storm(self, name: str, call_idx: int,
                     changed: List[Dict[str, str]], reg) -> None:
        with self._lock:
            st = self._funcs[name]
            st.recent.append(
                (call_idx, tuple(c["arg"] for c in changed)))
            window = [(i, args) for i, args in st.recent
                      if call_idx - i < self.storm_window]
            if len(window) < self.storm_threshold:
                return
            # culprit: the argument changing most often across the storm
            freq: Dict[str, int] = {}
            for _i, args in window:
                for a in args:
                    freq[a] = freq.get(a, 0) + 1
            st.storms += 1
            st.recent.clear()  # re-arm
            retraces = len(window)
        culprits = sorted(freq, key=lambda a: (-freq[a], a))
        reg.counter(f"compile.storms[fn={name}]").inc()
        reg.emit("compile.retrace_storm", function=name,
                 retraces=retraces, window=self.storm_window,
                 culprits=culprits,
                 culprit=(culprits[0] if culprits else None),
                 last_changed=changed)
        from ..framework.log import vlog
        vlog(0, "observability: retrace storm on %s — %d retraces in "
             "%d calls, culprit argument %r", name, retraces,
             self.storm_window, culprits[0] if culprits else "?")


_tracker_lock = threading.Lock()
_tracker: Optional[CompileTracker] = None


def get_tracker() -> CompileTracker:
    """The process-global compile tracker (mirrors ``get_registry``)."""
    global _tracker
    with _tracker_lock:
        if _tracker is None:
            _tracker = CompileTracker()
        return _tracker


def reset_tracker() -> None:
    """Drop all per-function compile state (tests)."""
    get_tracker().reset()


def track_jit(fn: Callable, name: Optional[str] = None,
              arg_names: Optional[Sequence[str]] = None,
              tracker: Optional[CompileTracker] = None) -> Callable:
    """Wrap a jitted callable with compile/retrace accounting.

    The wrapper is transparent (same args/result).  A hit costs two
    reads of the jitted callable's cache size and a counter, whatever
    the arguments hold: signatures are built (one walk, linear in pytree
    leaves, no device sync) only when the cache grew during the call, on
    a name's first call, while a roofline capture is open, and on every
    call of a callable that has no such cache (a plain function).
    Misses are timed: on a fresh signature the call wall time is trace +
    XLA compile + first run, the honest per-backend compile-cost proxy.

    >>> step = track_jit(jax.jit(step), name="train_step",
    ...                  arg_names=("params", "batch"))
    """
    if name is None:
        name = getattr(fn, "__name__", None) or repr(fn)
    # how many traces jax.jit holds for this callable: the call traced
    # (or compiled anew) exactly when this grew
    cache_size = getattr(fn, "_cache_size", None)

    @functools.wraps(fn)
    def tracked(*args, **kwargs):
        tr = tracker or get_tracker()
        abstract = None
        if roofline.capture_active():
            try:
                # abstract shapes BEFORE the call: the roofline
                # observatory re-lowers this signature later, after any
                # donated buffers are dead (ISSUE 19)
                abstract = roofline.abstractify(args, kwargs)
            except Exception:
                abstract = None
        before = None if cache_size is None else cache_size()
        t0 = time.perf_counter()
        result = fn(*args, **kwargs)
        try:  # tracking must never break the call
            wall_ms = (time.perf_counter() - t0) * 1e3
            if (abstract is None and before is not None
                    and cache_size() == before and tr.note_hit(name)):
                return result
            # described AFTER the call: a donated array has lost its
            # buffer by now, not its shape and dtype
            sigs = [arg_signature(a) for a in args]
            sigs += [arg_signature(kwargs[k]) for k in sorted(kwargs)]
            names = list(arg_names) if arg_names else None
            if names is not None and kwargs:
                names = names[:len(args)] + sorted(kwargs)
            rec = tr.observe_signatures(sigs, name=name, arg_names=names,
                                        wall_ms=wall_ms)
            if abstract is not None:
                roofline.get_observatory().record(
                    name, fn, abstract[0], abstract[1],
                    sig_key=hash(tuple(sigs)), miss=rec is not None)
        except Exception as e:
            from ..framework.log import vlog
            vlog(1, "observability: compile tracking failed for %s: "
                 "%r", name, e)
        return result

    tracked.__tracked_name__ = name
    tracked.__wrapped_fn__ = fn
    return tracked
