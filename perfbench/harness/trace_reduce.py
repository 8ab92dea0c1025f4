"""From the profiler's ``.xplane.pb`` to busy and idle time, per-operation
sums, collective time and named idle gaps.

Two steps, so that the second can be checked on a small recorded trace:

1. :func:`load_xplane` reads the file with ``jax.profiler.ProfileData`` and
   keeps, as plain lists, the device planes' operation line and the harness's
   own host spans (``pb:<name>`` trace annotations, ``spans.py``);
2. :func:`reduce` turns those lists into numbers.  It knows nothing of jax.

Written once; a PR that claims a gain never edits it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from .spans import PREFIX

OPS_LINE = "XLA Ops"                    # a TPU device plane's operation line
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter|"
    r"collective-broadcast)")
KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq", "flash_decode",
           "paged_decode", "fused_ln_linear", "fused_linear_residual",
           "fused_ffn")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*\[[0-9,]*\])")


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def parse_op(text: str) -> Tuple[str, str]:
    """A TPU trace calls an operation by its whole HLO line:
    ``%copy.395 = bf16[1856,16,16,128]{3,2,1,0:T(8,128)} copy(bf16[...]
    %fusion.11)``.  Returns the instruction's own name and its result's
    (first) type and shape: ``("copy.395", "bf16[1856,16,16,128]")``.  The
    operands are dropped: they name OTHER operations, and a kernel must not
    be found in the line of the fusion that reads its output."""
    if text.startswith("%") and " = " in text:
        name, rest = text[1:].split(" = ", 1)
        m = _SHAPE_RE.search(rest.split("(", 1)[0] or rest)
        if m is None:
            m = _SHAPE_RE.search(rest)
        return name, (m.group(1) if m else "")
    return text, ""


def load_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": [{"name", "events": [[name, start_ns, dur_ns, detail]]}],
    "host": [[name, start_ns, dur_ns]]}`` — device operations (instruction
    name and result shape, ``parse_op``) of every
    ``/device:`` plane and the harness's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = [[*parse_op(e.name), float(e.start_ns),
                           float(e.duration_ns)] for e in line.events]
                events = [[n, s, d, shape] for n, shape, s, d in events]
                devices.append({"name": plane.name, "events": events})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name[len(PREFIX):], float(e.start_ns),
                                     float(e.duration_ns)])
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def describe_xplane(path: str, per_line: int = 6) -> str:
    """A page about a trace for a human: planes, lines, first events with
    their stats.  Look at one trace by hand before trusting a reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in dict(e.stats).items()}
                out.append(f"    {e.name!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} {stats}")
    return "\n".join(out)


def save_raw(raw: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)


def load_raw(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def cut(raw: Dict[str, Any], t0_ns: float, t1_ns: float) -> Dict[str, Any]:
    """The part of a trace that starts inside ``[t0_ns, t1_ns)``, host spans
    clipped to it — how a small fixture is cut from a recorded one."""
    return {
        "devices": [{"name": d["name"],
                     "events": [e for e in d["events"]
                                if t0_ns <= e[1] < t1_ns]}
                    for d in raw["devices"]],
        "host": [[h[0], max(h[1], t0_ns),
                  min(h[1] + h[2], t1_ns) - max(h[1], t0_ns)]
                 for h in raw["host"]
                 if h[1] < t1_ns and h[1] + h[2] > t0_ns],
    }


# -- the arithmetic ---------------------------------------------------------
def stable_name(name: str, detail: str = "") -> str:
    """An operation's name without the compiler's running number, with the
    result's type and shape: ``fusion.123`` + ``bf16[128,6144]`` ->
    ``fusion_bf16_128_6144``.  A Pallas kernel is called by its own name."""
    for k in KERNELS:
        if k in name:
            base = k
            break
    else:
        base = re.sub(r"[.\d]+$", "", name) or name
    if detail:
        base += "_" + re.sub(r"[^A-Za-z0-9]+", "_", detail).strip("_")
    return base


def kernel_of(name: str) -> Optional[str]:
    for k in KERNELS:
        if k in name:
            return k
    return None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def self_times(events: List[list]) -> List[float]:
    """Each event's duration less the part its children cover (an event
    that lies inside another, as the body of a ``while`` does), so that sums
    by name do not count a loop and its body twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selft = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            pend = events[p][1] + events[p][2]
            selft[p] -= max(0.0, min(s + d, pend) - s)
        stack.append(i)
    return [max(0.0, x) for x in selft]


def _clip(events: List[list], t0: float, t1: float) -> List[list]:
    out = []
    for e in events:
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a:
            out.append([e[0], a, b - a] + list(e[3:]))
    return out


def reduce(raw: Dict[str, Any], window_span: str = "traced",
           top: int = 10) -> Dict[str, Any]:
    """Numbers of one traced stretch.

    The stretch is the harness's ``pb:<window_span>`` host span where the
    trace has one, else from the first device operation to the end of the
    last.  Times are seconds.  ``busy_s`` is the union of the intervals in
    which an operation ran, averaged over the devices; ``ops`` sums self
    time by stable name on the first device; ``kernels`` by Pallas kernel
    name, with call counts; ``collective_s`` is self time in collectives
    averaged over the devices; ``idle_gaps`` names each idle gap of the
    first device by the harness span that covers most of it.
    """
    devices = [d for d in raw["devices"] if d["events"]]
    if not devices:
        return {"devices": 0, "window_s": 0.0, "busy_s": 0.0}
    spans = [h for h in raw["host"] if h[0] == window_span]
    if spans:
        t0 = min(h[1] for h in spans)
        t1 = max(h[1] + h[2] for h in spans)
    else:
        t0 = min(e[1] for d in devices for e in d["events"])
        t1 = max(e[1] + e[2] for d in devices for e in d["events"])
    window = max(t1 - t0, 0.0)

    busy, coll = [], []
    for d in devices:
        ev = _clip(d["events"], t0, t1)
        merged = union([(e[1], e[1] + e[2]) for e in ev])
        busy.append(sum(b - a for a, b in merged))
        st = self_times(ev)
        coll.append(sum(s for e, s in zip(ev, st)
                        if COLLECTIVE_RE.match(e[0])))

    first = _clip(devices[0]["events"], t0, t1)
    st = self_times(first)
    ops: Dict[str, List[float]] = {}
    kernels: Dict[str, List[float]] = {}
    for e, s in zip(first, st):
        rec = ops.setdefault(stable_name(e[0], e[3] if len(e) > 3 else ""),
                             [0, 0.0])
        rec[0] += 1
        rec[1] += s
        k = kernel_of(e[0])
        if k:
            krec = kernels.setdefault(k, [0, 0.0])
            krec[0] += 1
            krec[1] += s

    merged = union([(e[1], e[1] + e[2]) for e in first])
    gaps, cursor = [], t0
    for a, b in merged:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        gaps.append((cursor, t1))
    host = [h for h in raw["host"] if h[0] != window_span]
    by_span: Dict[str, float] = {}
    for a, b in gaps:
        best, best_ov, best_len = "_no_span_", 0.0, float("inf")
        for name, hs, hd in host:
            ov = min(b, hs + hd) - max(a, hs)
            # most of the gap; of two that cover it, the inner (shorter) one
            if ov > 0 and (ov > best_ov + 1e-3 * (b - a)
                           or (abs(ov - best_ov) <= 1e-3 * (b - a)
                               and hd < best_len)):
                best, best_ov, best_len = name, ov, hd
        if best_ov < 0.5 * (b - a):
            best = "_no_span_"
        by_span[best] = by_span.get(best, 0.0) + (b - a)

    ns = 1e-9
    n = len(devices)
    op_rows = sorted(((f"{k}__x{c}", s * ns) for k, (c, s) in ops.items()),
                     key=lambda r: -r[1])
    return {
        "devices": n,
        "window_s": window * ns,
        "busy_s": sum(busy) / n * ns,
        "collective_s": sum(coll) / n * ns,
        "ops": {k: [c, s * ns] for k, (c, s) in ops.items()},
        "kernels": {k: [c, s * ns] for k, (c, s) in kernels.items()},
        "device_ops": [[k, s] for k, s in op_rows[:top]],
        "idle_gaps": [[k, s * ns] for k, s in
                      sorted(by_span.items(), key=lambda r: -r[1])[:top]],
    }
