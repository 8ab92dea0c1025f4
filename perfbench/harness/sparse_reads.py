"""Readers of what a model with an indexer adds (ISSUE 32):
``layer_metrics/dsa_*.longctx.py`` are a line each over these.  Every reader
returns None where it finds nothing to read (a program without the kernels,
the spans' attributes or the counters), and the harness leaves the metric
out.

``dsa_sparse_attn``'s time is the kernel's own: the XLA gather that hands it
the selected rows has no stable name in a trace and shows in the run's
``breakdown`` (PERF.md section 7).
"""
from __future__ import annotations

from typing import Optional

from . import phase_reads
from .arith import roofline_share
from .expert_reads import _model_counts, kernel_time
from .sparse_arith import index_scores_cost, kept_share, sparse_attn_cost

INDEX_KERNEL = "dsa_index_scores"
ATTEND_KERNEL = "dsa_sparse_attn"
SELECTED = "serve.dsa_selected_tokens"
CONTEXT = "serve.dsa_context_tokens"


def _traced_counter(run, attr: str) -> Optional[int]:
    """``attr`` summed over the program's ``engine.step`` spans that lie
    inside the traced stretch (the engine sets a step's counters on its
    span under the name after their last dot).  Steps cut by the
    stretch's edges are left out, so the sum errs low."""
    traced = [r for r in run["spans"].records if r[0] == "traced"]
    between = getattr(phase_reads._source(), "spans_between", None)
    if not traced or between is None:
        return None
    _, a, b = traced[-1]
    total = sum(int(at[attr]) for path, t0, t1, at in between(a, b)
                if path.rsplit("/", 1)[-1] == phase_reads.ROOT
                and attr in at and a <= t0 and t1 <= b)
    return total or None


def _share(run, kernel: str, cost) -> Optional[float]:
    if run["job"] != "serve" or not run["peaks"]:
        return None
    k = kernel_time(run, kernel)
    if k is None:
        return None
    work = cost(run["shape"])
    if work is None:
        return None
    share, _ = roofline_share(work[0], work[1], k[1],
                              run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share


def dsa_index_roofline(run) -> Optional[float]:
    """The index kernel's least time over its summed device time, in
    percent: the larger of the live index keys' bytes over the HBM peak
    and the index products' FLOPs over the bf16 peak, the live tokens
    counted by the program (``serve.dsa_context_tokens``: entries scored,
    summed over rows, layers and the traced decode steps)."""
    def cost(shape):
        scored = _traced_counter(run, CONTEXT.rsplit(".", 1)[-1])
        if scored is None or "index_heads" not in shape:
            return None
        return index_scores_cost(scored, shape["index_heads"],
                                 shape["index_dim"])
    return _share(run, INDEX_KERNEL, cost)


def dsa_sparse_attn_roofline(run) -> Optional[float]:
    """The sparse attention kernel's least time over its summed device
    time, in percent: the selected rows' bytes over the HBM peak or the
    absorbed form's FLOPs over the bf16 peak, the rows counted by the
    program (``serve.dsa_selected_tokens``)."""
    def cost(shape):
        rows = _traced_counter(run, SELECTED.rsplit(".", 1)[-1])
        if rows is None or "kv_lora_rank" not in shape:
            return None
        return sparse_attn_cost(
            rows, shape["heads"],
            shape["kv_lora_rank"] + shape["qk_rope_head_dim"],
            shape["kv_lora_rank"])
    return _share(run, ATTEND_KERNEL, cost)


def dsa_kept_share(run) -> Optional[float]:
    """Entries attended over entries scored by the decode steps since the
    window opened (``engine.stats()["model_counts"]``), in percent."""
    m0, m1 = (_model_counts(run, "stats_at_open"),
              _model_counts(run, "stats_at_end"))
    if not m1 or CONTEXT not in m1["counters"]:
        return None
    c0 = (m0 or {}).get("counters", {})
    return kept_share(m1["counters"][SELECTED] - c0.get(SELECTED, 0),
                      m1["counters"][CONTEXT] - c0.get(CONTEXT, 0))
