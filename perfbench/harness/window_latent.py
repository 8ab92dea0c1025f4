"""What the latent window decode kernel (``mla_window_decode``) has to
do in a decode step, from shapes and counters alone, and its reader
(``layer_metrics/mla_window_decode_roofline.agentctx.py`` is a line over
it).

A window layer keeps one latent row a token, ``kv_lora_rank +
qk_rope_head_dim`` values (1,024 + 64 in dots3's sliding layers), shared
by all heads; in the absorbed form every head multiplies its query row with
the rows of the tokens inside the window and its probabilities with their
first ``kv_lora_rank`` values:

    bytes = tokens * latent_width * dtype_bytes
    FLOPs = tokens * 2 * heads * (latent_width + value_width)

``tokens`` are the cached tokens INSIDE the window, ``min(context,
window)`` a row, summed over the decoded rows and the window layers (the
program counts them: ``attn_window_tokens``).  The kernel fetches whole
pages around the window (a window of 513 in pages of 128 lies in five pages
of 640 tokens, the window's bytes about 80% of them); those bytes are not
work the layer asks for and are left out, as ``gqa_arith`` leaves them out.
Queries in and outputs out are left out, as the latent kernel's are.

The reader returns None where it finds nothing to read (a program without
the kernel, or without the spans' attribute), and the harness leaves the
metric out.
"""
from __future__ import annotations

from typing import Optional, Tuple

from .arith import roofline_share
from .expert_reads import kernel_time
from .sparse_reads import _traced_counter

KERNEL = "mla_window_decode"
TOKENS = "attn_window_tokens"


def window_decode_cost(tokens: float, heads: int, latent_width: int,
                       value_width: int, dtype_bytes: int = 2
                       ) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of attending ``tokens`` cached tokens."""
    return (tokens * 2.0 * heads * (latent_width + value_width),
            tokens * float(latent_width) * dtype_bytes)


def mla_window_decode_roofline(run) -> Optional[float]:
    """The window kernel's least time over its summed device time in the
    traced decode steps, in percent: the larger of the window's latent
    bytes over the HBM peak and the absorbed form's FLOPs over the bf16
    peak, the tokens counted by the program (``serve.attn_window_tokens``:
    summed over rows, window layers and steps)."""
    if run["job"] != "serve" or not run["peaks"]:
        return None
    k = kernel_time(run, KERNEL)
    shape = run["shape"]
    if k is None or "window_kv_lora_rank" not in shape:
        return None
    tokens = _traced_counter(run, TOKENS)
    if tokens is None:
        return None
    rank = shape["window_kv_lora_rank"]
    flops, moved = window_decode_cost(
        tokens, shape["window_heads"],
        rank + shape["window_qk_rope_head_dim"], rank)
    share, _ = roofline_share(flops, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share
