"""Grouped matrix products over rows sorted by expert (ISSUE 28).

A dropless expert layer sorts its (token, expert) pairs by expert and
multiplies each expert's rows by that expert's weights.  Two routes, one
row layout (``nn/dropless_moe.py`` builds it):

- :func:`grouped_swiglu` / :func:`grouped_matmul` with ``tile > 1`` — the
  Pallas kernels ``moe_grouped_swiglu`` and ``moe_grouped_down``.  Every
  expert's rows are padded to a multiple of ``tile``, so a tile of rows
  belongs to ONE expert and a grid step is a plain ``(tile, K) x (K, tn)``
  product: no masks, no accumulator.  ``tile_expert`` (scalar prefetch)
  names each tile's expert; tiles past ``tiles_used`` are clamped onto the
  last used tile, which costs neither a DMA nor a product, and their
  output rows are never written — the caller gathers only rows it placed.
  The grid walks output columns outermost and row tiles innermost:
  consecutive tiles of one expert find its ``(K, tn)`` weight block
  already in VMEM, so an expert's weights are read once whatever its
  load, and an expert with no rows is never read at all.
- ``tile == 1`` — ``jax.lax.ragged_dot`` over the compact rows (group
  sizes are the experts' counts).  The route off the TPU, and the
  kernels' oracle.

Decode batches give an expert about ten rows, so the layer is bound by
reading weights; the kernels stream them once at full-K blocks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import _interpret

__all__ = ["grouped_swiglu", "grouped_matmul"]

_VMEM_LIMIT = 96 * 1024 * 1024


def _col_tile(n: int, want: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is <= ``want``
    (``n`` itself when it has none: small test widths)."""
    t = (min(want, n) // 128) * 128
    while t >= 128:
        if n % t == 0:
            return t
        t -= 128
    return n


def _swiglu_kernel(te_ref, used_ref, x_ref, wg_ref, wu_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _matmul_kernel(te_ref, used_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _grouped_call(kernel, name, x, weights, tile_expert, tiles_used, tile,
                  col_tile, interpret):
    from jax.experimental.pallas import tpu as pltpu
    m, k = x.shape
    n = weights[0].shape[2]
    tn = _col_tile(n, col_tile)
    tiles = m // tile

    def row(mi, used):        # tiles past the used ones sit on the last
        return jnp.minimum(mi, jnp.maximum(used[0] - 1, 0))

    x_spec = pl.BlockSpec((tile, k),
                          lambda ni, mi, te, used: (row(mi, used), 0))
    w_spec = pl.BlockSpec(
        (1, k, tn), lambda ni, mi, te, used: (te[row(mi, used)], 0, ni))
    o_spec = pl.BlockSpec(
        (tile, tn), lambda ni, mi, te, used: (row(mi, used), ni))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tn, tiles),
        in_specs=[x_spec] + [w_spec] * len(weights), out_specs=o_spec)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype), name=name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(tiles_used, (1,)).astype(jnp.int32), x, *weights)


def grouped_swiglu(x, w_gate, w_up, group_sizes, tile_expert, tiles_used,
                   tile: int, col_tile: int = 512,
                   interpret: Optional[bool] = None):
    """``silu(x @ w_gate[e]) * (x @ w_up[e])`` for rows ``x (M, K)``
    grouped by expert, weights ``(E, K, F)``: ``(M, F)``."""
    if tile == 1:
        g = lax.ragged_dot(x, w_gate, group_sizes,
                           preferred_element_type=jnp.float32)
        u = lax.ragged_dot(x, w_up, group_sizes,
                           preferred_element_type=jnp.float32)
        return (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    return _grouped_call(_swiglu_kernel, "moe_grouped_swiglu", x,
                         (w_gate, w_up), tile_expert, tiles_used, tile,
                         col_tile, interpret)


def grouped_matmul(x, w, group_sizes, tile_expert, tiles_used, tile: int,
                   col_tile: int = 1024, interpret: Optional[bool] = None):
    """``x @ w[e]`` for rows grouped by expert: ``(M, N)``."""
    if tile == 1:
        return lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
    return _grouped_call(_matmul_kernel, "moe_grouped_down", x, (w,),
                         tile_expert, tiles_used, tile, col_tile, interpret)
