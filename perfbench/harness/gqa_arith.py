"""What a grouped-query paged decode kernel (``gqa_full_decode``,
``gqa_window_decode``) has to do in a decode step, from shapes and counters
alone, the same whatever implements it.

A layer keeps ``kv_heads`` keys of ``qk_dim`` and values of ``v_dim`` a
token; every query head multiplies its one query with the keys of the tokens
it attends and their probabilities with the values:

    bytes = tokens * kv_heads * (qk_dim + v_dim) * dtype_bytes
    FLOPs = tokens * 2 * heads * (qk_dim + v_dim)

``tokens`` are the cached tokens ATTENDED, summed over the decoded rows and
the layers of the kind: a full layer's row attends its whole context, a
window layer's ``min(context, window)`` (the program counts both:
``attn_full_tokens`` / ``attn_window_tokens``).  A window kernel also has to
fetch whole pages around its window; those bytes are not work the layer asks
for and are left out, so a small window in large pages reads low.  Queries in
and outputs out (under a hundredth of a page's bytes a row) are left out, as
the latent kernel's are.

``held_share``: the bytes the pools hold under the rows of the decode steps
over what ONE table for every layer would hold for the same rows, in
percent: each kind's blocks times its block's bytes, against the full
kind's blocks (every token of every row) times all kinds' block bytes.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple


def gqa_decode_cost(tokens: float, heads: int, kv_heads: int, qk_dim: int,
                    v_dim: int, dtype_bytes: int = 2) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of attending ``tokens`` cached tokens."""
    return (tokens * 2.0 * heads * (qk_dim + v_dim),
            tokens * float(kv_heads * (qk_dim + v_dim)) * dtype_bytes)


def held_share(blocks: Dict[str, float], block_bytes: Dict[str, float],
               every_token: str = "full") -> Optional[float]:
    """``blocks[kind]``: a kind's blocks under the decoded rows;
    ``block_bytes[kind]``: what one of them takes over the kind's layers."""
    one_table = blocks.get(every_token, 0) * sum(block_bytes.values())
    if one_table <= 0:
        return None
    return 100.0 * sum(n * block_bytes[k] for k, n in blocks.items()) \
        / one_table
