"""What the grouped expert product (``moe_grouped_swiglu`` +
``moe_grouped_down``) has to do, from the router's counts: ``pairs``
(token, held expert) pairs computed and ``experts_touched`` held experts
that got at least one of them, both summed over the expert layers.

    FLOPs = 6 * pairs * hidden * width      (gate, up and down products)
    bytes = experts_touched * 3 * hidden * width * dtype_bytes
            + pairs * 2 * hidden * dtype_bytes
            (each touched expert's three matrices once; a pair's row in
            and its row out.  An expert no token chose is never read, so
            it is not counted: a thin batch cannot read over 100%)
"""
from __future__ import annotations

from typing import Tuple


def grouped_expert_cost(pairs: float, experts_touched: float, hidden: int,
                        width: int, dtype_bytes: int = 2
                        ) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of the expert products behind those counts."""
    flops = 6.0 * pairs * hidden * width
    moved = (experts_touched * 3.0 * hidden * width
             + pairs * 2.0 * hidden) * dtype_bytes
    return flops, moved
