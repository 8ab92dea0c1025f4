"""What a Mamba-2 model's decode kernel and its latent experts have to do,
from the program's counts.

``ssm_state_decode``, over ``rows`` (a row's state stepped once in one
layer, summed over the layers and the traced decode steps):

    bytes = rows * (2 * heads * head_dim * state * 4      the float32 state
                                                         read and written
                    + (2 * heads * head_dim + heads
                       + 2 * groups * state) * 4)          x, dt, B, C in and
                                                         y out, float32
    FLOPs = rows * heads * head_dim * state * 4            decay, the outer
                                                         product's add, the
                                                         read-out's product
                                                         and sum

The latent experts (``moe_grouped_relu2`` + ``moe_grouped_down``), from the
router's counts as ``moe_arith.py`` takes them, at the latent width ``io``
and no gate:

    FLOPs = 4 * pairs * io * width          (up and down products)
    bytes = experts_touched * 2 * io * width * dtype_bytes
            + pairs * 2 * io * dtype_bytes  (each touched expert's two
                                             matrices once; a pair's row in
                                             and its row out)
"""
from __future__ import annotations

from typing import Tuple


def ssm_decode_cost(rows: float, heads: int, head_dim: int, state: int,
                    groups: int) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of the decode kernel over ``rows`` row-layers."""
    moved = rows * (2.0 * heads * head_dim * state
                    + 2.0 * heads * head_dim + heads
                    + 2.0 * groups * state) * 4
    flops = rows * 4.0 * heads * head_dim * state
    return flops, moved


def latent_expert_cost(pairs: float, experts_touched: float, io: int,
                       width: int, dtype_bytes: int = 2
                       ) -> Tuple[float, float]:
    """``(FLOPs, bytes)`` of the latent relu2 experts behind those
    counts."""
    flops = 4.0 * pairs * io * width
    moved = (experts_touched * 2.0 * io * width
             + pairs * 2.0 * io) * dtype_bytes
    return flops, moved
