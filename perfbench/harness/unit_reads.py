"""What the serving engine's ledger of its units says (ISSUE 37): the
readers behind ``layer_metrics/device_starved_share.*``,
``host_slack_share.*`` and ``prefill_device_share.*``, each a line over
these, and ``queue_wait_ms`` for a cell whose requests queue.

Since ISSUE 36 a ``step()`` call launches unit n+1 and then lands unit n, so
a call is no unit.  The engine stamps each unit twice on
``time.perf_counter()`` (handed to the device: the end of its ``dispatch``;
seen done: the end of its ``device_wait``) and sums what follows
(``engine.stats()["units"]``, taken at the window's opening and the run's
end: ``serve.stats_at_open`` / ``serve.stats_at_end``):

- ``wait_s``: the host's ``device_wait`` for a unit.  Over the seconds
  inside ``step()`` it is the host's SLACK: the share of its time in which
  it had nothing to do but wait for the chip.  It is a gauge of HEADROOM,
  not a score: a device-side gain can show end to end only while it is well
  above 0, and such a gain LOWERS it (a faster kernel shortens the wait and
  leaves the host's own milliseconds as they were), as a host-side
  regression does.  ``engine_units`` therefore says ``host_ms_a_unit`` too,
  the host's own milliseconds a landed unit, which only the host's side
  moves.
- ``device_s`` (+ ``device_s_bound``): a unit's time on the device as the
  host can see it, ``done(n) - max(done(n-1), enqueued(n))``, by kind and,
  a prefill's, by bucket.
- ``starved``: ``{why: [intervals, seconds]}`` before units launched with
  nothing in flight: the device had nothing of this engine's to run, and
  why (``idle``: nothing to plan, all the time between calls included;
  ``preempt``; ``fault``; ``drain``; ``start``).  The call whose launch
  ended such an interval carries ``starved_t0`` / ``starved_t1`` /
  ``starved_why`` on its ``engine.step`` span, so the intervals can be cut
  to any stretch.

What the host cannot see, and ``device_starved_share`` therefore lacks of
``device_idle_share``: gaps INSIDE a unit (between two kernels of one
program) and between two units that were both handed over in time; the lag
between the device finishing and the host's wait returning (tens of
microseconds an interval, which makes an interval start late); the gap
before a unit that was launched ahead of a landing the host came late to
(``host_late`` counts those; 0 while the host keeps ahead of the device:
on the chip machine the process stands still for ~120 ms a few times a run,
and each time the device idles for that less the unit in flight: 81-136 ms
of a 3 s stretch, 2.7-4.6 points, when one falls in it; PERF.md section 6).

A program whose ``stats()`` has no ``units`` or whose tracing has no
``record`` (an older commit) reads None, as does a span ring that no longer
holds the stretch, and the harness leaves the metric out.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Optional, Tuple

from . import phase_reads
from .arith import percentile
from .reads import note

QUEUE = "engine.request/queue"
INF = float("inf")


def _ends(run) -> Optional[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """The ledger at the window's opening and at the run's end."""
    s = run["serve"] if run["job"] == "serve" else None
    end = ((s or {}).get("stats_at_end") or {}).get("units")
    if not end:
        return None
    return (s.get("stats_at_open") or {}).get("units") or {}, end


def _sum(u: Dict[str, Any], key: str, kind: Optional[str] = None) -> float:
    return sum(v[key] for k, v in u.get("by_kind", {}).items()
               if kind in (None, k))


def _device(u: Dict[str, Any], kind: Optional[str] = None) -> float:
    return _sum(u, "device_s", kind) + _sum(u, "device_s_bound", kind)


def _starved(u: Dict[str, Any]) -> float:
    return sum(s for _, s in u.get("starved", {}).values())


def _open(u: Dict[str, Any]) -> float:
    """Seconds of the starved interval still open at the snapshot."""
    return u["now_s"] - u["last_done_s"] if u.get("starving") else 0.0


def _seen(u: Dict[str, Any]) -> float:
    """Up to when a snapshot accounts: its own instant with nothing in
    flight, else the last landing (the unit in flight is booked later)."""
    return u["now_s"] if u["starving"] else u["last_done_s"]


def _means(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Of the units landed between two rows of sums: how many, their mean
    rows, mean wait and mean time on the device (over the exact ones)."""
    n = b["units"] - a.get("units", 0)
    exact = n - (b["units_bound"] - a.get("units_bound", 0))
    d = lambda key: b[key] - a.get(key, 0.0)
    return {"units": n, "units_bound": n - exact,
            "rows": d("rows") / n if n else None,
            "device_ms": 1e3 * d("device_s") / exact if exact else None,
            "wait_ms": 1e3 * d("wait_s") / n if n else None}


def host_slack_share(run) -> Optional[float]:
    """Seconds the host waited for the device over seconds inside
    ``step()``, window's opening to run's end, in percent: the host's
    headroom behind the device.  Read it as a gauge before a device-side
    claim, not as a score after one: the gain it vouches for lowers it."""
    ends = _ends(run)
    if ends is None:
        return None
    a, b = ends
    inside = b["step_s"] - a.get("step_s", 0.0)
    if inside <= 0.0:
        return None
    return 100.0 * (_sum(b, "wait_s") - _sum(a, "wait_s")) / inside


def prefill_device_share(run) -> Optional[float]:
    """Prefill units' time on the device over all units', opening to end,
    in percent.  Says the units once, on a line ``engine_units``: by kind
    and by prefill bucket how many landed, their mean rows, wait and time
    on the device; ``host_late``; ``host_ms_a_unit``, the seconds inside
    ``step()`` that were no wait, a landed unit; and ``covered``, what the
    ledger accounts for (on the device or starved, the interval open at
    either end included) over the wall between the two snapshots (up to a
    snapshot's last landing where a unit was in flight), in percent.
    ``covered`` is a check of the BOOKKEEPING: the sums telescope, so it
    reads 100 unless a landing was dropped or booked twice (a fault, a
    replay); it is no evidence that a unit's time was spent computing."""
    ends = _ends(run)
    if ends is None:
        return None
    a, b = ends
    if "_engine_units" not in run:
        wall = _seen(b) - _seen(a) if a else 0.0
        took = (_device(b) + _starved(b) + _open(b)
                - _device(a) - _starved(a) - _open(a))
        buckets = {str(k): _means(a.get("prefill_by_bucket", {}).get(k, {}),
                                  v)
                   for k, v in sorted(b["prefill_by_bucket"].items())}
        by_kind = {k: _means(a.get("by_kind", {}).get(k, {}), v)
                   for k, v in b["by_kind"].items()}
        landed = sum(m["units"] for m in by_kind.values())
        own = ((b["step_s"] - _sum(b, "wait_s"))
               - (a.get("step_s", 0.0) - _sum(a, "wait_s")))
        run["_engine_units"] = {
            "by_kind": by_kind,
            "prefill_by_bucket": {k: m for k, m in buckets.items()
                                  if m["units"]},     # warmed-up ones only
            "host_late": b["host_late"] - a.get("host_late", 0),
            "host_ms_a_unit": 1e3 * own / landed if landed else None,
            "wall_s": wall,
            "covered": 100.0 * took / wall if wall > 0 else None}
        print("engine_units: " + json.dumps(run["_engine_units"]),
              flush=True)
    every = _device(b) - _device(a)
    if every <= 0.0:
        return None
    return 100.0 * (_device(b, "prefill") - _device(a, "prefill")) / every


def device_starved_share(run) -> Optional[float]:
    """Seconds of the traced stretch in which the engine had handed the
    device nothing (the ``starved_*`` attributes of the ``engine.step``
    spans, cut to the stretch; the interval open at the run's end from the
    ledger) over the stretch as the trace measured it, in percent: the
    stretch and the denominator of ``device_idle_share.*``, to be read
    beside it.  Says the window's intervals by reason once, on a line
    ``engine_starved``: ``{why: [intervals, seconds]}``, opening to end."""
    ends, t = _ends(run), run["trace"]
    if ends is None:
        return None
    at_open, end = ends
    before = at_open.get("starved", {})
    print("engine_starved: " + json.dumps(
        {why: [n - before.get(why, (0, 0.0))[0],
               s - before.get(why, (0, 0.0))[1]]
         for why, (n, s) in end["starved"].items()}), flush=True)
    traced = [r for r in run["spans"].records if r[0] == "traced"]
    src = phase_reads._source()
    between = getattr(src, "spans_between", None)
    if not t or not t.get("window_s") or not traced or between is None:
        return None
    _, a, b = traced[-1]
    if src.dropped(a):
        return None
    # an interval is on the root of the call that ENDED it: look past b
    starved = sum(
        max(0.0, min(at["starved_t1"], b) - max(at["starved_t0"], a))
        for path, _, _, at in between(a, INF)
        if phase_reads._split(path)[1] == phase_reads.ROOT
        and "starved_t0" in at)
    if end.get("starving"):
        starved += max(0.0, b - max(end["last_done_s"], a))
    return 100.0 * starved / t["window_s"]


def queue_wait_ms(run, q: float, name: str) -> Optional[float]:
    """A request's wait from ``submit()`` to the plan that took it (the
    program's ``engine.request/queue`` spans), over the waits that ended
    inside the window, in milliseconds.  No entry of ``BENCHMARK.json``
    reads it yet: the one serving cell with arrivals, ``gpt3-xl.chat``,
    hands a request over between two calls and the next call plans it, so
    the wait there is the ``reap`` phase (0.3 ms on the chip) and what a
    user waits before that is ``gen_late_ms_p90``'s.  It is for a cell
    whose requests queue inside the engine (PERF.md section 7)."""
    if run["job"] != "serve":
        return None
    src = phase_reads._source()
    if not hasattr(src, "record"):
        return None
    off = phase_reads.clock_offset(run)
    if off is None:
        return None
    a, b = off + run["window"]["t0"], off + run["window"]["t1"]
    if src.dropped(a):
        return None
    waits = [t1 - t0 for path, t0, t1, _ in src.spans_between(a, b)
             if path == QUEUE and a <= t1 < b]
    note(name, len(waits))
    v = percentile(waits, q)
    return None if v is None else 1e3 * v
