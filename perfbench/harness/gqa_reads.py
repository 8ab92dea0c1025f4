"""Readers of what a model with grouped-query window and full layers over a
page pool a layer kind adds (ISSUE 35): ``layer_metrics/gqa_*.mixedctx.py``
and ``kv_held_share.mixedctx.py`` are a line each over these.  Every reader
returns None where it finds nothing to read (a program without the kernels,
the spans' attributes or the pools' counts), and the harness leaves the
metric out.
"""
from __future__ import annotations

import json
from typing import Optional

from .arith import roofline_share
from .expert_reads import kernel_time
from .gqa_arith import gqa_decode_cost, held_share
from .sparse_reads import _traced_counter

FULL_KERNEL = "gqa_full_decode"
WINDOW_KERNEL = "gqa_window_decode"


def _decode_roofline(run, kernel: str, counter: str,
                     kv_heads: str) -> Optional[float]:
    if run["job"] != "serve" or not run["peaks"]:
        return None
    k = kernel_time(run, kernel)
    shape = run["shape"]
    if k is None or kv_heads not in shape:
        return None
    tokens = _traced_counter(run, counter)
    if tokens is None:
        return None
    flops, moved = gqa_decode_cost(tokens, shape["heads"], shape[kv_heads],
                                   shape["qk_head_dim"], shape["v_head_dim"])
    share, _ = roofline_share(flops, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share


def gqa_full_decode_roofline(run) -> Optional[float]:
    """The full layers' kernel's least time over its summed device time in
    the traced decode steps, in percent: the larger of the attended
    tokens' key and value bytes over the HBM peak and the products' FLOPs
    over the bf16 peak, the tokens counted by the program
    (``serve.attn_full_tokens``: summed over rows, layers and steps)."""
    return _decode_roofline(run, FULL_KERNEL, "attn_full_tokens",
                            "full_kv_heads")


def gqa_window_decode_roofline(run) -> Optional[float]:
    """The same for the window layers' kernel, over the tokens inside the
    window (``serve.attn_window_tokens``: ``min(context, window)`` a row a
    layer)."""
    return _decode_roofline(run, WINDOW_KERNEL, "attn_window_tokens",
                            "window_kv_heads")


def say_kv_pools(run) -> None:
    """One line ``kv_pools``: each pool's blocks, high water, blocks freed
    behind the window and block bytes at the end of the run."""
    s = run["serve"] if run["job"] == "serve" else None
    end = ((s or {}).get("stats_at_end") or {}).get("kv_pools")
    if end:
        print("kv_pools: " + json.dumps(end), flush=True)


def kv_held_share(run) -> Optional[float]:
    """The bytes both pools held under the rows of the decode steps since
    the window opened, over what one table for all layers would have held
    for them (``engine.stats()["kv_pools"]``), in percent."""
    s = run["serve"] if run["job"] == "serve" else None
    end = ((s or {}).get("stats_at_end") or {}).get("kv_pools")
    if not end or len(end) < 2:
        return None
    at_open = (s.get("stats_at_open") or {}).get("kv_pools") or {}
    blocks = {k: p["blocks_live"] - at_open.get(k, {}).get("blocks_live", 0)
              for k, p in end.items()}
    return held_share(blocks, {k: p["block_bytes"] for k, p in end.items()})
