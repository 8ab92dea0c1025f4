"""Readers of what a latent-attention, expert-layer model adds (ISSUE 28):
``layer_metrics/*.reason.py`` are a line each over these.  Every reader
returns None where it finds nothing to read (a program without the kernel,
the spans' attributes or the counters), and the harness leaves the metric
out.

Kernel times are found by prefix among ``run["trace"]["ops"]`` (stable
names: kernel name + result shape), because ``trace_reduce.KERNELS`` lists
only the kernels the benchmark started with.
"""
from __future__ import annotations

import json
from typing import Optional, Tuple

from . import phase_reads
from .arith import roofline_share
from .latent_arith import latent_decode_cost
from .moe_arith import grouped_expert_cost

LATENT_KERNEL = "mla_latent_attn"
EXPERT_KERNELS = "moe_grouped"


def kernel_time(run, prefix: str) -> Optional[Tuple[int, float]]:
    """``(calls, seconds)`` summed over the traced operations whose stable
    name starts with ``prefix``."""
    t = run["trace"]
    if not t:
        return None
    hits = [v for k, v in t.get("ops", {}).items() if k.startswith(prefix)]
    calls, secs = sum(v[0] for v in hits), sum(v[1] for v in hits)
    return (int(calls), float(secs)) if calls and secs > 0 else None


def mla_decode_roofline(run) -> Optional[float]:
    """The latent kernel's least time over its summed device time, in
    percent: per call the larger of the latent bytes of the decoded rows
    over the HBM peak and the absorbed form's FLOPs over the bf16 peak,
    each call taken at the mean live context of the traced decode steps."""
    s = run["serve"] if run["job"] == "serve" else None
    if s is None or not run["peaks"] or not s["traced"]:
        return None
    k = kernel_time(run, LATENT_KERNEL)
    if k is None:
        return None
    a, b = s["traced"]
    live = [lv for (ts, te, kind, _, lv) in s["steps"]
            if kind == "decode" and a <= ts and te <= b]
    shape = run["shape"]
    if not live or "kv_lora_rank" not in shape:
        return None
    flops, moved = latent_decode_cost(
        k[0] * sum(live) / len(live), shape["heads"],
        shape["kv_lora_rank"] + shape["qk_rope_head_dim"],
        shape["kv_lora_rank"])
    share, _ = roofline_share(flops, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share


def traced_expert_counts(run) -> Optional[Tuple[int, int]]:
    """``(pairs, experts touched)`` summed over the program's
    ``engine.step`` spans that lie inside the traced stretch (the
    attributes ``moe_pairs`` / ``moe_experts_touched`` the engine sets
    from the step's own outputs).  Steps cut by the stretch's edges are
    left out, so the count errs low."""
    traced = [r for r in run["spans"].records if r[0] == "traced"]
    between = getattr(phase_reads._source(), "spans_between", None)
    if not traced or between is None:
        return None
    _, a, b = traced[-1]
    pairs = touched = 0
    for path, t0, t1, at in between(a, b):
        if (path.rsplit("/", 1)[-1] == phase_reads.ROOT and "moe_pairs" in at
                and a <= t0 and t1 <= b):
            pairs += int(at["moe_pairs"])
            touched += int(at["moe_experts_touched"])
    return (pairs, touched) if pairs else None


def moe_expert_roofline(run) -> Optional[float]:
    """The grouped expert products' least time over their summed device
    time in the traced stretch, in percent."""
    if run["job"] != "serve" or not run["peaks"]:
        return None
    k = kernel_time(run, EXPERT_KERNELS)
    counts = traced_expert_counts(run)
    shape = run["shape"]
    if k is None or counts is None or "expert_width" not in shape:
        return None
    flops, moved = grouped_expert_cost(counts[0], counts[1], shape["hidden"],
                                       shape["expert_width"])
    share, _ = roofline_share(flops, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share


LOAD_GAUGE = "serve.moe_load_max_over_mean"


def _model_counts(run, when: str) -> Optional[dict]:
    s = run["serve"] if run["job"] == "serve" else None
    return ((s or {}).get(when) or {}).get("model_counts")


def moe_load_max_over_mean(run) -> Optional[float]:
    """Routing imbalance: a decode step's busiest held expert over the
    mean, averaged over the expert layers and over the decode steps since
    the window opened (``engine.stats()["model_counts"]``)."""
    m0, m1 = (_model_counts(run, "stats_at_open"),
              _model_counts(run, "stats_at_end"))
    if not m0 or not m1 or LOAD_GAUGE not in m1["gauges"]:
        return None
    g1 = m1["gauges"][LOAD_GAUGE]
    g0 = m0["gauges"].get(LOAD_GAUGE, {"sum": 0.0, "steps": 0})
    steps = g1["steps"] - g0["steps"]
    return (g1["sum"] - g0["sum"]) / steps if steps > 0 else None


def say_engine_counts(run) -> None:
    """One line ``engine_counts`` of what a run of this cell has to show
    beside its metrics: the expert layers' counters (dropped pairs stay
    0), preemptions since the window opened, the pool's high water."""
    m1 = _model_counts(run, "stats_at_end")
    if not m1:
        return
    s = run["serve"]
    end, at_open = s["stats_at_end"], s["stats_at_open"] or {}
    print("engine_counts: " + json.dumps({
        **m1["counters"],
        "preemptions_since_open": end["preemptions"]
        - at_open.get("preemptions", 0),
        "kv_blocks_high_water": end["kv_blocks"]["high_water"],
        "kv_blocks_total": end["kv_blocks"]["total"],
        "kv_bytes_per_token": end.get("kv_bytes_per_token"),
        "model_gauges": end.get("model_gauges")}), flush=True)
