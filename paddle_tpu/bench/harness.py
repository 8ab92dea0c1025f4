"""Shared scenario harness (ISSUE 13).

One measurement discipline for every scenario, so rows are comparable:

- :func:`measure_steps` — the timed loop.  Each step is decomposed into
  the ledger's phase axes: **data** (host batch production), **compute**
  (the dispatch call), **readback** (the host readback of the loss —
  dispatch is asynchronous, so this is where the host waits for the
  device).  The **collective** phase comes from the
  ``collective.<op>.ms`` histogram deltas the comm layer records across
  the timed window.
- :class:`CompileWindow` — brackets a scenario with a compile-tracker
  reset and registry-counter baselines, yielding the row's ``compile``
  stats (wall, traces, retraces, in-process cache hits, persistent
  disk-cache hits/requests from ``observability/compilecache``).
- :func:`peak_hbm` — PJRT ``memory_stats()`` peak when the backend
  exposes it, else the compiled program's memory analysis
  (temp+argument+output bytes), the platform-independent proxy.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..observability.registry import split_labels

__all__ = ["measure_steps", "CompileWindow", "RooflineWindow", "peak_hbm",
           "xla_memory", "bytes_on_wire", "pct"]


def pct(sorted_vals: List[float], p: float) -> Optional[float]:
    """The percentile definition shared with ``aggregate._pct``."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _collective_ms_total(registry) -> float:
    """Sum of all ``collective.<op>.ms`` histogram totals right now —
    labeled (``[axis=..,n=..]``) and legacy-unlabeled families both
    count, each exactly once."""
    total = 0.0
    for name, snap in registry.snapshot().items():
        base, _labels = split_labels(name)
        if (base.startswith("collective.") and base.endswith(".ms")
                and snap.get("type") == "histogram"):
            total += float(snap.get("sum") or 0.0)
    return total


def _collective_by_key(registry) -> Dict[Tuple[str, Optional[str], int],
                                         Dict[str, float]]:
    """Per-(op, axis, participants) totals of the collective instrument
    families right now: ``{"ms": histogram sum, "calls": counter,
    "bytes": counter}``.  Unlabeled legacy names land under
    ``axis=None, participants=0`` — one bucket, never double-counted
    against their labeled siblings (distinct instrument names)."""
    out: Dict[Tuple[str, Optional[str], int], Dict[str, float]] = {}
    for name, snap in registry.snapshot().items():
        base, labels = split_labels(name)
        if not base.startswith("collective."):
            continue
        parts = base.split(".")
        if len(parts) != 3 or parts[2] not in ("ms", "calls", "bytes"):
            continue
        op, field = parts[1], parts[2]
        try:
            n = int(labels.get("n", "0"))
        except ValueError:
            n = 0
        key = (op, labels.get("axis"), n)
        rec = out.setdefault(key, {"ms": 0.0, "calls": 0.0, "bytes": 0.0})
        if field == "ms":
            if snap.get("type") == "histogram":
                rec["ms"] += float(snap.get("sum") or 0.0)
        elif snap.get("type") == "counter":
            rec[field] += float(snap.get("value") or 0.0)
    return out


def measure_steps(step_fn: Callable[[int, Any], Any],
                  make_batch: Callable[[int], Any],
                  steps: int, warmup: int,
                  registry=None) -> Dict[str, Any]:
    """Run ``warmup + steps`` iterations; time the last ``steps`` with a
    per-phase breakdown.

    ``make_batch(i)`` produces one host-side batch (its wall time is the
    **data** phase); ``step_fn(i, batch)`` dispatches one step, keeping
    any state (params/opt) internal, and returns the scalar to read back
    (**compute** = the dispatch call, **readback** = ``float(...)`` on
    the result).  Returns per-step series plus phase p50s shaped for
    ``schema.new_row``.
    """
    if registry is None:
        from ..observability import get_registry
        registry = get_registry()
    t0 = time.perf_counter()
    out = None
    for i in range(warmup):
        out = step_fn(i, make_batch(i))
    if out is not None:
        float(out)                      # true sync before the timed window
    warm_s = time.perf_counter() - t0

    total_ms: List[float] = []
    data_ms: List[float] = []
    compute_ms: List[float] = []
    readback_ms: List[float] = []
    coll_by0 = _collective_by_key(registry)
    last = None
    for i in range(steps):
        ta = time.perf_counter()
        batch = make_batch(warmup + i)
        tb = time.perf_counter()
        out = step_fn(warmup + i, batch)
        tc = time.perf_counter()
        last = float(out) if out is not None else None
        td = time.perf_counter()
        data_ms.append((tb - ta) * 1e3)
        compute_ms.append((tc - tb) * 1e3)
        readback_ms.append((td - tc) * 1e3)
        total_ms.append((td - ta) * 1e3)
    coll_by1 = _collective_by_key(registry)
    collective_by_op: List[Dict[str, Any]] = []
    coll_total = 0.0
    for key in sorted(coll_by1, key=lambda k: (k[0], str(k[1]), k[2])):
        rec = coll_by1[key]
        base0 = coll_by0.get(key, {"ms": 0.0, "calls": 0.0, "bytes": 0.0})
        d_ms = max(0.0, rec["ms"] - base0["ms"])
        d_calls = max(0.0, rec["calls"] - base0["calls"])
        d_bytes = max(0.0, rec["bytes"] - base0["bytes"])
        coll_total += d_ms
        if d_ms <= 0.0 and d_calls <= 0.0 and d_bytes <= 0.0:
            continue
        op, axis, n = key
        collective_by_op.append({
            "op": op, "axis": axis, "participants": n or None,
            "calls": d_calls / max(1, steps),
            "ms": d_ms / max(1, steps),
            "payload_bytes": d_bytes / max(1, steps),
        })
    collective_per_step = coll_total / max(1, steps)

    def p50(series: List[float]) -> float:
        return pct(sorted(series), 50) or 0.0

    return {
        "step_times_ms": total_ms,
        "phases_ms": {"data": p50(data_ms), "compute": p50(compute_ms),
                      "readback": p50(readback_ms),
                      "collective": collective_per_step},
        "collective_by_op": collective_by_op,
        "warmup_s": warm_s,
        "final_value": last,
    }


class CompileWindow:
    """Bracket one scenario: tracker reset on entry, compile stats for
    the row on :meth:`stats`.

    Wall time is the delta of the ``compile.wall_ms[fn=...]`` histogram
    totals (the registry is process-global and scenarios run back to
    back); trace/retrace/hit counts come from the tracker, which IS
    reset per scenario; persistent-cache hits/requests are the
    ``observability/compilecache`` counter deltas.
    """

    def __init__(self, registry=None):
        if registry is None:
            from ..observability import get_registry
            registry = get_registry()
        self._registry = registry

    def __enter__(self) -> "CompileWindow":
        from ..observability.compilation import reset_tracker
        reset_tracker()
        self._wall0 = self._compile_wall_total()
        self._pc0 = self._persistent_counts()
        return self

    def __exit__(self, *exc) -> None:
        return None

    def _compile_wall_total(self) -> float:
        total = 0.0
        for name, snap in self._registry.snapshot().items():
            if (name.startswith("compile.wall_ms[")
                    and snap.get("type") == "histogram"):
                total += float(snap.get("sum") or 0.0)
        return total

    def _persistent_counts(self) -> Tuple[float, float]:
        reg = self._registry
        return (reg.counter("compile.persistent_cache_hits").value,
                reg.counter("compile.persistent_cache_requests").value)

    def stats(self) -> Dict[str, Any]:
        from ..observability.compilation import get_tracker
        tr = get_tracker()
        traces = retraces = calls = storms = 0
        for fn in tr.functions():
            st = tr.stats(fn)
            calls += st["calls"]
            traces += st["traces"]
            retraces += st["retraces"]
            storms += st["storms"]
        hits, reqs = self._persistent_counts()
        return {
            "wall_ms": max(0.0, self._compile_wall_total() - self._wall0),
            "traces": traces,
            "retraces": retraces,
            "storms": storms,
            "cache_hits": max(0, calls - traces),
            "persistent_hits": int(hits - self._pc0[0]),
            "persistent_requests": int(reqs - self._pc0[1]),
        }


class RooflineWindow:
    """Bracket one scenario with the MFU-microscope capture (ISSUE 19):
    on entry the roofline observatory starts recording the abstract
    signatures ``track_jit`` sees; :meth:`block` lowers + compiles each
    captured program (outside any timed region) and returns the row's
    ``roofline`` gap-budget block.  Never raises — a failed capture
    degrades to the phase-only block so the row still validates.
    """

    def __enter__(self) -> "RooflineWindow":
        from ..observability import roofline
        self._win = roofline.capture_window()
        self._win.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._win.__exit__(*exc)

    def block(self, step_times_ms: List[float],
              phases_ms: Dict[str, float], *,
              padding_frac: float = 0.0) -> Dict[str, Any]:
        p50 = pct(sorted(float(t) for t in step_times_ms), 50) or 0.0
        try:
            return self._win.build_block(p50, phases_ms,
                                         padding_frac=padding_frac)
        except Exception as e:
            from ..observability import roofline
            return roofline.degraded_block(
                p50, phases_ms, padding_frac=padding_frac,
                reason=f"capture failed: {e!r}")


def xla_memory(jitted, *args) -> Optional[Dict[str, int]]:
    """Compiled-program memory analysis (temp/argument/output bytes) —
    None when the backend doesn't expose it."""
    try:
        fn = getattr(jitted, "__wrapped_fn__", jitted)
        mem = fn.lower(*args).compile().memory_analysis()
        return {"temp_bytes": int(mem.temp_size_in_bytes),
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes)}
    except Exception:
        return None


def peak_hbm(jitted=None, *args) -> Optional[int]:
    """Peak device memory for the row: the live PJRT watermark when the
    backend reports one, else the compiled program's static footprint."""
    import jax
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:
        stats = None
    if stats and stats.get("peak_bytes_in_use"):
        return int(stats["peak_bytes_in_use"])
    if jitted is not None:
        mem = xla_memory(jitted, *args)
        if mem:
            return (mem["temp_bytes"] + mem["argument_bytes"]
                    + mem["output_bytes"])
    return None


def _counter_family_total(registry, base: str) -> float:
    """Sum of one counter family — the unlabeled ``base`` plus every
    ``base[...]`` labeled variant (each a distinct instrument)."""
    total = 0.0
    for name, snap in registry.snapshot().items():
        b, _labels = split_labels(name)
        if b == base and snap.get("type") == "counter":
            total += float(snap.get("value") or 0.0)
    return total


class BytesOnWire:
    """Delta reader over the comm package's trace-time byte accounting
    (PR 8): ``comm.compressed_bytes`` is what the run ships,
    ``comm.bytes`` the exact-schedule equivalent.  Both are summed as
    metric *families* — since ISSUE 20 the counters carry
    ``[axis=..,leg=..]`` labels."""

    def __init__(self, registry=None):
        if registry is None:
            from ..observability import get_registry
            registry = get_registry()
        self._registry = registry
        self._raw0 = _counter_family_total(registry, "comm.bytes")
        self._wire0 = _counter_family_total(registry,
                                            "comm.compressed_bytes")

    def delta(self) -> int:
        reg = self._registry
        wire = (_counter_family_total(reg, "comm.compressed_bytes")
                - self._wire0)
        raw = _counter_family_total(reg, "comm.bytes") - self._raw0
        return int(wire if wire > 0 else raw)


def bytes_on_wire(registry=None) -> BytesOnWire:
    return BytesOnWire(registry)
