"""What several metric readers share: each ``end_to_end/<name>.py`` and
``layer_metrics/<name>.py`` is a few lines over these.  A reader that finds
nothing to read returns None and the harness leaves the metric out."""
from __future__ import annotations

from typing import List, Optional

from .arith import (kv_bytes_per_token, percentile, roofline_share,
                    tpot_per_request)


def note(name: str, n: int) -> None:
    """The number of samples behind a percentile, on an earlier line."""
    print(f"samples_behind: {name} n={n}", flush=True)


def idle_share(run) -> Optional[float]:
    t = run["trace"]
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _serve(run):
    return run["serve"] if run["job"] == "serve" else None


def window_requests(run) -> List[dict]:
    w = run["window"]
    return [r for r in run["serve"]["requests"]
            if w["t0"] <= r["due"] < w["t1"]]


def tpot_ms(run, q: float, name: str) -> Optional[float]:
    s = _serve(run)
    if s is None:
        return None
    w = run["window"]
    per_req = tpot_per_request([r["times"] for r in s["requests"]],
                               w["t0"], w["t1"], min_gaps=8)
    note(name, len(per_req))
    v = percentile(per_req, q)
    return None if v is None else 1e3 * v


def ttft_ms(run, q: float, name: str) -> Optional[float]:
    if _serve(run) is None:
        return None
    waits = [r["first"] - r["due"] for r in window_requests(run)
             if r["first"] is not None]
    note(name, len(waits))
    v = percentile(waits, q)
    return None if v is None else 1e3 * v


def gen_late_ms(run, q: float, name: str) -> Optional[float]:
    if _serve(run) is None:
        return None
    late = [r["submitted"] - r["due"] for r in window_requests(run)]
    note(name, len(late))
    v = percentile(late, q)
    return None if v is None else 1e3 * v


def served_tokens(run) -> Optional[int]:
    """Prompt tokens whose prefill step ended inside the window plus output
    tokens emitted inside it (a request's first output token is emitted by
    its prefill step).  A preempted sequence's second prefill serves nothing
    new and is not counted."""
    s = _serve(run)
    if s is None:
        return None
    w = run["window"]
    total = 0
    for r in s["requests"]:
        if r["first"] is not None and w["t0"] <= r["first"] < w["t1"]:
            total += r["prompt_len"]
        total += sum(1 for x in r["times"] if w["t0"] <= x < w["t1"])
    return total


def decode_occupancy(run) -> Optional[float]:
    """Mean running sequences over ``max_seqs``, decode steps that ended
    inside the window only, in percent."""
    s = _serve(run)
    if s is None:
        return None
    w = run["window"]
    rows = [n for (_, te, kind, n, _) in s["steps"]
            if kind == "decode" and w["t0"] <= te < w["t1"]]
    if not rows:
        return None
    return 100.0 * sum(rows) / len(rows) / s["max_seqs"]


def prefill_time_share(run) -> Optional[float]:
    """Host time inside prefill steps over the window, in percent."""
    s = _serve(run)
    if s is None:
        return None
    w = run["window"]
    inside = sum(max(0.0, min(te, w["t1"]) - max(ts, w["t0"]))
                 for (ts, te, kind, _, _) in s["steps"] if kind == "prefill")
    return 100.0 * inside / w["seconds"]


def paged_decode_roofline(run) -> Optional[float]:
    """Bytes of live keys and values the traced decode steps had to read
    (from each decoded sequence's length) over the HBM peak, over the summed
    device time of ``paged_decode``, in percent.  The kernel runs once per
    layer per decode step; the steps traced are its calls over the layers,
    each taken at the mean live length of the decode steps in the stretch."""
    s, t = _serve(run), run["trace"]
    if s is None or not t or not run["peaks"] or not s["traced"]:
        return None
    k = t.get("kernels", {}).get("paged_decode")
    if not k or k[1] <= 0:
        return None
    a, b = s["traced"]
    live = [lv for (ts, te, kind, _, lv) in s["steps"]
            if kind == "decode" and a <= ts and te <= b]
    if not live:
        return None
    shape = run["shape"]
    steps = k[0] / shape["layers"]
    moved = steps * (sum(live) / len(live)) * kv_bytes_per_token(
        shape["layers"], shape["heads"], shape["head_dim"])
    share, _ = roofline_share(0.0, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share
