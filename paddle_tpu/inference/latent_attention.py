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

Its neighbours, for a model with an indexer, are ``dsa_index_scores`` and
``dsa_sparse_attn`` in ``sparse_attention.py`` (scores over every live
index key; this kernel's body over the selected rows only).

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
           "latent_attention_reference"]


def _check_shapes(q, pages, block_tables, seq_lens, value_width):
    b, h, w = q.shape
    enforce(pages.ndim == 3 and pages.shape[2] == w,
            f"latent pages {pages.shape} disagree with q {q.shape}")
    enforce(0 < value_width <= w, f"value width {value_width} of {w}")
    enforce(block_tables.shape[0] == b and seq_lens.shape == (b,),
            f"tables {block_tables.shape} / lens {seq_lens.shape} "
            f"disagree with batch {b}")


def latent_attention_reference(q, pages, block_tables, seq_lens,
                               value_width: int, scale: float):
    """``q (batch, heads, width)`` against ``pages (num_blocks,
    block_size, width)``: ``(batch, heads, value_width)``.  A row with
    ``seq_lens[b] == 0`` (decode-batch padding) returns zeros."""
    _check_shapes(q, pages, block_tables, seq_lens, value_width)
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


def _latent_kernel(lens_ref, table_ref, q_ref, kv_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, block_size,
                   value_width):
    b = pl.program_id(0)
    t = pl.program_id(1)
    kv_len = lens_ref[b]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    @pl.when(t * block_size < kv_len)
    def _step():
        q = q_ref[0]                                   # (heads, width)
        kv = kv_ref[0]                                 # (block, width)
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        cols = t * block_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, _NEG_INF)      # (heads, block)
        m_prev = m_scr[...]                            # (heads, _LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(cols < kv_len, jnp.exp(s - m_new[:, :1]), 0.0)
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
                            name: str = "mla_latent_attn"):
    """``name`` is the kernel's name in a trace: ``sparse_attention.py``
    runs this body over gathered rows as ``dsa_sparse_attn``."""
    from jax.experimental.pallas import tpu as pltpu
    _check_shapes(q, pages, block_tables, seq_lens, value_width)
    b, h, w = q.shape
    block_size = pages.shape[1]
    max_blocks = block_tables.shape[1]

    def page_index(bi, ti, lens, tbl):
        last = jnp.maximum((lens[bi] + block_size - 1) // block_size - 1, 0)
        return (tbl[bi, jnp.minimum(ti, last)], 0, 0)

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
                               value_width=value_width)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        name=name,
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(block_tables, jnp.int32), q, pages)


def latent_attention(q, pages, block_tables, seq_lens, value_width: int,
                     scale: float):
    """The kernel on a TPU, the reference elsewhere (interpret-mode
    Pallas is orders slower than XLA on the CPU)."""
    if jax.default_backend() == "tpu":
        return latent_attention_pallas(q, pages, block_tables, seq_lens,
                                       value_width, scale)
    return latent_attention_reference(q, pages, block_tables, seq_lens,
                                      value_width, scale)
