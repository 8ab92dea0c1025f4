"""Ragged paged decode over LATENT pages (ISSUE 28): multi-head latent
attention in its absorbed form.

A latent cache keeps one row a token, shared by every head: ``[c_kv |
k_rope | padding]``, ``width`` values of which the first ``value_width``
are also the values.  With the key and value up-projections absorbed into
the query and the output, a decode step computes, per sequence ``b`` and
for all heads at once,

    s      = q[b] (heads, width) . rows[b]^T (width, context) * scale
    out[b] = softmax(s) . rows[b][:, :value_width]

so, unlike ``paged_attention`` (one query row per head against that head's
own keys: matrix-vector work on the VPU), the heads form the rows of a
real matrix product against the shared page and the kernel runs on the
MXU, near the chip's ridge (2 * heads FLOPs a byte read).

- :func:`latent_attention_pallas` — the kernel ``mla_latent_attn``.  Grid
  ``(batch, table entries)`` with the lengths and the block table as
  scalar prefetch; one page ``(block_size, width)`` a grid step.  Entries
  past a row's last page are clamped onto that page in the index map (an
  unchanged block index is not fetched again) and their update is
  predicated off, so a dead entry costs a grid step and nothing else.
  The flash ``(m, l, acc)`` state lives in VMEM across a row's entries.
- :func:`latent_attention_reference` — gather + masked softmax in
  ``jax.numpy``, float32 at ``HIGHEST``: the route off the TPU and the
  kernel's oracle.
- With ``window`` (a layer whose query at ``i`` attends ``j`` with
  ``0 <= i - j < window``) the same body is ``mla_window_decode``: the
  block table is a RING of ``kv_cache.window_table_width`` columns, block
  ``b`` of the sequence at column ``b % width``, and grid entry ``t`` of a
  row reads block ``(len - window) // block_size + t``, so the kernel walks
  only the columns the window reaches (dead entries clamped and predicated
  off as above) and masks positions before ``len - window``.

Its neighbours, for a model with an indexer, are ``dsa_index_scores`` and
``dsa_sparse_attn`` in ``sparse_attention.py`` (scores over every live
index key; the live latent pages in waves, under the indexer's selection).

``width`` is a multiple of 128 (a 576-value row is stored in 640): the
page's minor dimension is then whole lane tiles, which is what keeps XLA
from giving the donated pool another layout than the kernel's (PERF.md
section 7 row 6 has what a minor dimension of 64 did).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..framework.errors import enforce
from ..ops.flash_attention import _interpret, _LANES, _NEG_INF

__all__ = ["latent_attention", "latent_attention_pallas",
           "latent_attention_reference", "latent_window_attention_pallas"]


def _check_shapes(q, pages, block_tables, seq_lens, value_width):
    b, h, w = q.shape
    enforce(pages.ndim == 3 and pages.shape[2] == w,
            f"latent pages {pages.shape} disagree with q {q.shape}")
    enforce(0 < value_width <= w, f"value width {value_width} of {w}")
    enforce(block_tables.shape[0] == b and seq_lens.shape == (b,),
            f"tables {block_tables.shape} / lens {seq_lens.shape} "
            f"disagree with batch {b}")


def latent_attention_reference(q, pages, block_tables, seq_lens,
                               value_width: int, scale: float,
                               window: Optional[int] = None):
    """``q (batch, heads, width)`` against ``pages (num_blocks,
    block_size, width)``: ``(batch, heads, value_width)``.  A row with
    ``seq_lens[b] == 0`` (decode-batch padding) returns zeros.  With
    ``window`` the table is a ring and a row attends its last ``window``
    tokens."""
    _check_shapes(q, pages, block_tables, seq_lens, value_width)
    if window is not None:
        return _window_reference(q, pages, block_tables, seq_lens,
                                 value_width, scale, window)
    block_size = pages.shape[1]
    max_ctx = block_tables.shape[1] * block_size

    def per_seq(qb, table, ln):
        rows = jnp.take(pages, table, axis=0).reshape(max_ctx, -1)
        rows = rows.astype(jnp.float32)
        s = jnp.einsum("hw,lw->hl", qb.astype(jnp.float32), rows,
                       precision=lax.Precision.HIGHEST) * scale
        valid = (jnp.arange(max_ctx) < ln)[None, :]
        s = jnp.where(valid, s, _NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(valid, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        out = jnp.einsum("hl,lv->hv", p, rows[:, :value_width],
                         precision=lax.Precision.HIGHEST)
        return (out / jnp.maximum(l, 1e-30)).astype(q.dtype)

    return jax.vmap(per_seq)(q, block_tables, seq_lens)


def _window_reference(q, pages, block_tables, seq_lens, value_width, scale,
                      window):
    bs, width = pages.shape[1], block_tables.shape[1]

    def per_seq(qb, table, ln):
        rows = jnp.take(pages, table, axis=0).reshape(width * bs, -1)
        # column c of the ring holds the one block b = c (mod width) among
        # the last `width` of the sequence's blocks
        hi = -(-ln // bs)
        block = hi - 1 - jnp.mod(hi - 1 - jnp.arange(width), width)
        pos = (block[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        valid = (pos >= 0) & (pos < ln) & (pos >= ln - window)
        # a column the row does not hold may be anyone's: 0 * NaN is NaN
        rows = jnp.where(valid[:, None], rows, 0).astype(jnp.float32)
        s = jnp.einsum("hw,lw->hl", qb.astype(jnp.float32), rows,
                       precision=lax.Precision.HIGHEST) * scale
        s = jnp.where(valid[None, :], s, _NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(valid[None, :], jnp.exp(s - m), 0.0)
        out = jnp.einsum("hl,lv->hv", p, rows[:, :value_width],
                         precision=lax.Precision.HIGHEST)
        l = jnp.sum(p, axis=1, keepdims=True)
        return (out / jnp.maximum(l, 1e-30)).astype(q.dtype)

    return jax.vmap(per_seq)(q, block_tables, seq_lens)


def _first_block(kv_len, window, block_size):
    """The first block a row's window reaches."""
    return jnp.maximum(kv_len - window, 0) // block_size


def _seen(cols, kv_len, window):
    """Which positions ``cols`` a row of ``kv_len`` tokens attends."""
    seen = cols < kv_len
    if window is not None:
        seen &= cols >= kv_len - window
    return seen


def _latent_kernel(lens_ref, table_ref, q_ref, kv_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, block_size,
                   value_width, window=None):
    b = pl.program_id(0)
    t = pl.program_id(1)
    kv_len = lens_ref[b]
    # the row's block that entry t holds: t, or in a window's ring the
    # window's first block + t
    blk = t if window is None else t + _first_block(kv_len, window,
                                                    block_size)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    @pl.when(blk * block_size < kv_len)
    def _step():
        q = q_ref[0]                                   # (heads, width)
        kv = kv_ref[0]                                 # (block, width)
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        cols = blk * block_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # (the mask is built twice, as this body always built it: the
        # full kernel's program is pinned by tests/test_latent_programs.py)
        s = jnp.where(_seen(cols, kv_len, window), s,
                      _NEG_INF)                        # (heads, block)
        m_prev = m_scr[...]                            # (heads, _LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(_seen(cols, kv_len, window),
                      jnp.exp(s - m_new[:, :1]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p.astype(kv.dtype), kv[:, :value_width],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def latent_attention_pallas(q, pages, block_tables, seq_lens,
                            value_width: int, scale: float,
                            interpret: Optional[bool] = None,
                            name: str = "mla_latent_attn",
                            window: Optional[int] = None):
    """``name`` is the kernel's name in a trace:
    :func:`latent_window_attention_pallas` runs this body over a ring as
    ``mla_window_decode``."""
    from jax.experimental.pallas import tpu as pltpu
    _check_shapes(q, pages, block_tables, seq_lens, value_width)
    b, h, w = q.shape
    block_size = pages.shape[1]
    max_blocks = block_tables.shape[1]

    def page_index(bi, ti, lens, tbl):
        last = jnp.maximum((lens[bi] + block_size - 1) // block_size - 1, 0)
        if window is None:
            return (tbl[bi, jnp.minimum(ti, last)], 0, 0)
        blk = jnp.minimum(_first_block(lens[bi], window, block_size) + ti,
                          last)
        return (tbl[bi, lax.rem(blk, max_blocks)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # seq_lens, block_tables
        grid=(b, max_blocks),
        in_specs=[pl.BlockSpec((1, h, w), lambda bi, ti, lens, tbl:
                               (bi, 0, 0)),
                  pl.BlockSpec((1, block_size, w), page_index)],
        out_specs=pl.BlockSpec((1, h, value_width),
                               lambda bi, ti, lens, tbl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, _LANES), jnp.float32),        # m
            pltpu.VMEM((h, _LANES), jnp.float32),        # l
            pltpu.VMEM((h, value_width), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_latent_kernel, scale=scale,
                               block_size=block_size,
                               value_width=value_width, window=window)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        name=name,
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(block_tables, jnp.int32), q, pages)


@functools.partial(jax.jit, static_argnames=("value_width", "scale",
                                             "window", "interpret"))
def latent_window_attention_pallas(q, pages, block_tables, seq_lens,
                                   value_width: int, scale: float,
                                   window: int,
                                   interpret: Optional[bool] = None):
    """The kernel over a window's ring, ``mla_window_decode``; jitted, so
    that a step program lowers it once for all its window layers."""
    return latent_attention_pallas(q, pages, block_tables, seq_lens,
                                   value_width, scale, interpret=interpret,
                                   name="mla_window_decode", window=window)


def latent_attention(q, pages, block_tables, seq_lens, value_width: int,
                     scale: float, window: Optional[int] = None):
    """The kernel on a TPU, the reference elsewhere (interpret-mode
    Pallas is orders slower than XLA on the CPU)."""
    if jax.default_backend() != "tpu":
        return latent_attention_reference(q, pages, block_tables, seq_lens,
                                          value_width, scale, window)
    if window is None:
        return latent_attention_pallas(q, pages, block_tables, seq_lens,
                                       value_width, scale)
    return latent_window_attention_pallas(
        q, pages, block_tables, seq_lens, value_width=int(value_width),
        scale=float(scale), window=int(window))
