"""What the latent decode kernel (``mla_latent_attn``) has to do, from
shapes alone: the absorbed form of multi-head latent attention reads one
latent row a cached token, shared by all heads, and multiplies it twice.

Counted per kernel call (one layer of one decode step) over the ``tokens``
of context the decoded rows hold:

    bytes = tokens * latent_width * dtype_bytes
            (the row's own values - 576 for DeepSeek-V2 - whatever the
            page pads them to; queries in and outputs out are left out:
            under a thousandth of the rows at these contexts)
    FLOPs = tokens * 2 * heads * (latent_width + value_width)
            (scores over the whole row, then probabilities times its
            first ``value_width`` values)
"""
from __future__ import annotations

from typing import Tuple


def latent_decode_cost(tokens: float, heads: int, latent_width: int,
                       value_width: int, dtype_bytes: int = 2
                       ) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of one call over ``tokens`` cached tokens."""
    flops = tokens * 2.0 * heads * (latent_width + value_width)
    moved = tokens * float(latent_width) * dtype_bytes
    return flops, moved
