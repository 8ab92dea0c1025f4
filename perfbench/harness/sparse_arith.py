"""What learned sparse attention (``dsa_index_scores``, the selection,
``dsa_sparse_attn``) has to do in a decode step, from shapes and counters
alone, the same whatever implements it.

The indexer reads one index key a live cached token and multiplies it with
every index head of the row's one query:

    bytes = tokens * index_dim * dtype_bytes
    FLOPs = tokens * 2 * index_heads * index_dim

(the ReLU, the head weights and the sum over heads are ``index_heads``
more operations a token: under a hundredth, left out; queries in and scores
out, 4 bytes a token, are left out of the bytes as the latent kernel's are).

Attention in the absorbed form reads one latent row a SELECTED token,
shared by all heads, and multiplies it twice, as ``latent_arith`` counts
for every cached token:

    bytes = rows * latent_width * dtype_bytes
    FLOPs = rows * 2 * heads * (latent_width + value_width)

``kept_share``: entries attended over entries scored, in percent; 100
where no context is longer than ``index_topk`` (the mechanism bypassed).
"""
from __future__ import annotations

from typing import Optional, Tuple


def index_scores_cost(tokens: float, index_heads: int, index_dim: int,
                      dtype_bytes: int = 2) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of scoring ``tokens`` live cached tokens."""
    return (tokens * 2.0 * index_heads * index_dim,
            tokens * float(index_dim) * dtype_bytes)


def sparse_attn_cost(rows: float, heads: int, latent_width: int,
                     value_width: int, dtype_bytes: int = 2
                     ) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of attending ``rows`` selected latent rows."""
    return (rows * 2.0 * heads * (latent_width + value_width),
            rows * float(latent_width) * dtype_bytes)


def kept_share(selected: float, scored: float) -> Optional[float]:
    return 100.0 * selected / scored if scored > 0 else None
