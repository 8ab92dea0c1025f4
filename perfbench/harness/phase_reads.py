"""Where the host time of ``ServingEngine.step`` goes: the readers of the
program's own spans (``layer_metrics/step_*_share.*.py`` are a line each
over these).

The program times each step as one ``engine.step`` span with a child per
phase (``reap``, ``schedule``, ``tables``, ``h2d``, ``dispatch``,
``device_wait``, ``logits_copy``, ``guard``, ``accept``, ``gauges``, rarely
``quarantine`` / ``recover``), kept in memory on ``time.perf_counter()``
and handed out by ``paddle_tpu.observability.tracing.spans_between``.  The
harness's times are relative to the start of its loop; its own
``engine.step`` records (``run["spans"].records``) are absolute and in the
order of ``run["serve"]["steps"]``, so the two give the offset.

Shares are percent of the summed duration of the program's ``engine.step``
spans that ended inside the window; ``host_only_share`` is over the traced
stretch, the denominator of ``device_idle_share.*``, so that the two are
read side by side.  A program without ``spans_between`` (an older commit),
a buffer that no longer holds the window, or a run with no serving steps
reads None, and the harness leaves the metric out.
"""
from __future__ import annotations

import json
import statistics
from typing import Any, Dict, List, Optional, Tuple

ROOT = "engine.step"
DEVICE_SIDE = ("dispatch", "device_wait")   # the device may be busy in these

Span = Tuple[str, float, float, Dict[str, Any]]


def _source():
    """The program's span facility (a test puts its own here)."""
    from paddle_tpu.observability import tracing
    return tracing


def clock_offset(run) -> Optional[float]:
    """``perf_counter()`` seconds at the harness's time zero."""
    mine = [r for r in run["spans"].records if r[0] == ROOT]
    steps = run["serve"]["steps"]
    if not mine or len(mine) != len(steps):
        return None
    return statistics.median(
        ((a - s[0]) + (b - s[1])) / 2.0 for (_, a, b), s in zip(mine, steps))


def _split(path: str) -> Tuple[Optional[str], str]:
    """``(parent's name, own name)`` of a span's path."""
    parts = path.rsplit("/", 2)
    return (parts[-2] if len(parts) > 1 else None), parts[-1]


def steps_between(run, a: float, b: float, clip: bool = False
                  ) -> Optional[Dict[str, List[float]]]:
    """``{phase: [count, total_s, self_s]}`` of the program's steps between
    ``a`` and ``b`` (``perf_counter()`` seconds), the root under its own
    name with the unattributed time as its self time.  Without ``clip``
    the steps that ENDED in ``[a, b)`` count whole; with it every span is
    cut to ``[a, b)``."""
    src = _source()
    between = getattr(src, "spans_between", None)
    if between is None:
        return None
    spans: List[Span] = between(a, b)
    chosen = None
    if not clip:
        roots = [(t0, at.get("step")) for path, t0, t1, at in spans
                 if _split(path)[1] == ROOT and a <= t1 < b]
        chosen = {step for _, step in roots}
        # a step that began before ``a``: its first phases ended before it
        a = min([a] + [t0 for t0, _ in roots])
        spans = between(a, b)
    if src.dropped(a):
        return None
    out: Dict[str, List[float]] = {}
    nested: Dict[str, float] = {}
    for path, t0, t1, at in spans:
        parent, name = _split(path)
        if chosen is not None and at.get("step") not in chosen:
            continue
        dur = (max(0.0, min(t1, b) - max(t0, a)) if clip else t1 - t0)
        if name == ROOT or parent == ROOT:
            row = out.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += dur
        if parent is not None:
            nested[parent] = nested.get(parent, 0.0) + dur
    if ROOT not in out or out[ROOT][1] <= 0.0:
        return None
    for name, row in out.items():
        row[2] = max(0.0, row[1] - nested.get(name, 0.0))
    return out


def window_phases(run) -> Optional[Dict[str, List[float]]]:
    """The steps that ended inside the window; computed once a run, and
    printed then on a line of its own."""
    if run["job"] != "serve":
        return None
    if "_engine_phases" not in run:
        off = clock_offset(run)
        w = run["window"]
        ph = (None if off is None
              else steps_between(run, off + w["t0"], off + w["t1"]))
        run["_engine_phases"] = ph
        _say("engine_phases", ph)
    return run["_engine_phases"]


def _say(label: str, ph: Optional[Dict[str, List[float]]]) -> None:
    if ph is not None:
        print(label + ": " + json.dumps(
            {k: [v[0], round(v[1], 6), round(v[2], 6)]
             for k, v in sorted(ph.items())}), flush=True)


def share(run, *phases: str) -> Optional[float]:
    ph = window_phases(run)
    if ph is None:
        return None
    return 100.0 * sum(ph[p][1] for p in phases if p in ph) / ph[ROOT][1]


def plan_share(run) -> Optional[float]:
    return share(run, "reap", "schedule")


def inputs_share(run) -> Optional[float]:
    return share(run, "tables", "h2d")


def logits_copy_share(run) -> Optional[float]:
    return share(run, "logits_copy")


def accept_share(run) -> Optional[float]:
    return share(run, "guard", "accept", "gauges")


def host_only_share(run) -> Optional[float]:
    """Host time of the traced stretch spent in phases during which the
    device has nothing of this step to run (every child of ``engine.step``
    but ``dispatch`` and ``device_wait``), over the traced stretch as the
    trace measured it, in percent.  What ``device_idle_share.*`` holds
    beyond this lies inside ``dispatch`` / ``device_wait``.  The stretch's
    own split goes on a line ``engine_phases_traced``: with the window's it
    gives the steps per second with the profiler off and on."""
    t = run["trace"]
    if run["job"] != "serve" or not t or not t.get("window_s"):
        return None
    traced = [r for r in run["spans"].records if r[0] == "traced"]
    if not traced:
        return None
    ph = steps_between(run, traced[-1][1], traced[-1][2], clip=True)
    _say("engine_phases_traced", ph)
    if ph is None:
        return None
    host = sum(v[1] for k, v in ph.items()
               if k != ROOT and k not in DEVICE_SIDE)
    return 100.0 * host / t["window_s"]
