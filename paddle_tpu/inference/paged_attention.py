"""Ragged paged-attention decode (ISSUE 6).

The serving engine's decode batch is **ragged**: every sequence in the
batch attends to a different-length context, and that context lives in
shared fixed-size KV blocks addressed through a per-sequence block table
(``inference/kv_cache.py``).  This module computes, for a batch of
single-token queries,

    out[b] = softmax(q[b] · K[b]^T * scale) · V[b]

where ``K[b]/V[b]`` are gathered by ``block_tables[b]`` and truncated at
``seq_lens[b]`` — the TPU-native layout of PAPERS.md's *Ragged Paged
Attention* (block-tabled KV, ragged decode batches).

Two implementations behind one routing entry point:

- :func:`paged_attention_pallas` — the kernel, built on the same Pallas
  surface as ``ops/flash_attention.py`` (lane-broadcast statistics,
  online-softmax recurrence).  Pages are token-major, ``(num_blocks,
  block_size, heads, head_dim)`` (``inference/kv_cache.py``): one
  token's ``(heads, head_dim)`` slab is the minor tile, which is what
  lets the step program scatter new tokens into the pool in place.  The
  grid is ``(batch, max_blocks)`` with the block table and sequence
  lengths as **scalar-prefetch** operands, so the k/v BlockSpec index
  maps dereference the table and Mosaic DMAs exactly one KV block —
  ``(block_size, heads, head_dim)``, contiguous in HBM — per grid step.
  Per-step VMEM residency is one such block of K and one of V (double
  buffered: 4 · block_size · heads · head_dim elements, 256 KB at 16 ×
  16 × 128 bf16) plus the ``(heads, head_dim)`` f32 state, regardless of
  pool size.  A block past ``seq_lens[b]`` is skipped (its flash state
  update is predicated off; the redundant page-0 DMA it still costs is
  the ragged tax also paid by the upstream TPU kernel).  The
  one-row-per-head products run on the VPU in f32, and the block's
  tokens lie along the leading axis, so the max / sum / accumulate over
  them are elementwise across vregs.
- :func:`paged_attention_reference` — a pure ``jax.numpy``/``lax``
  gather-softmax with identical semantics.  It is the default off-TPU
  (interpret-mode Pallas is orders slower than XLA CPU), which is what
  lets the tier-1 CPU suite run the full serving path; it is also the
  numerics oracle the kernel is tested against.

Routing: :func:`paged_attention` picks the kernel on a TPU backend, the
reference elsewhere; ``PTPU_PAGED_KERNEL=pallas|reference`` forces one
(``chip_smoke.py`` runs one engine on each and compares their logits).
The kernel of a compiled decode step is the ``paged_decode``
``tpu_custom_call`` in its HLO.

Decode is memory-bound, so the win is never FLOPs — it is that the
gather never materializes a per-sequence contiguous KV copy in HBM.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..framework.errors import enforce
from ..ops.flash_attention import _interpret, _LANES, _NEG_INF

__all__ = ["paged_attention", "paged_attention_pallas",
           "paged_attention_reference"]

PAGED_KERNEL_ENV = "PTPU_PAGED_KERNEL"


def _check_shapes(q, k_pages, v_pages, block_tables, seq_lens,
                  block_size: int):
    b, h, d = q.shape
    enforce(k_pages.ndim == 4 and k_pages.shape == v_pages.shape,
            f"page shape mismatch: k={k_pages.shape} v={v_pages.shape}")
    enforce(k_pages.shape[1:] == (block_size, h, d),
            f"pages {k_pages.shape} disagree with q {q.shape} at "
            f"block_size {block_size}")
    enforce(block_tables.shape[0] == b and seq_lens.shape == (b,),
            f"tables {block_tables.shape} / lens {seq_lens.shape} "
            f"disagree with batch {b}")


# ---------------------------------------------------------------------------
# Reference: gather + masked softmax (the CPU serving path and the oracle)
# ---------------------------------------------------------------------------
def paged_attention_reference(q, k_pages, v_pages, block_tables, seq_lens,
                              block_size: int,
                              scale: Optional[float] = None):
    """Pure-jax ragged paged attention over ``(batch, heads, head_dim)``
    single-token queries against ``(num_blocks, block_size, heads,
    head_dim)`` pages.  A row with ``seq_lens[b] == 0`` (a padding row of
    the decode batch) returns zeros."""
    _check_shapes(q, k_pages, v_pages, block_tables, seq_lens, block_size)
    b, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    max_ctx = block_tables.shape[1] * block_size

    def per_seq(qb, table, ln):
        # (T,) block ids -> gathered (T, bs, h, d) -> token rows (T*bs, h, d)
        k = jnp.take(k_pages, table, axis=0).reshape(max_ctx, h, d)
        v = jnp.take(v_pages, table, axis=0).reshape(max_ctx, h, d)
        # an oracle computes in f32 for real: on a TPU an f32 einsum at
        # the default precision rounds its operands (the probabilities
        # below among them) to bf16 first
        s = jnp.einsum("hd,lhd->hl", qb.astype(jnp.float32),
                       k.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST) * scale
        valid = (jnp.arange(max_ctx) < ln)[None, :]
        s = jnp.where(valid, s, _NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(valid, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        out = jnp.einsum("hl,lhd->hd", p, v.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        return (out / jnp.maximum(l, 1e-30)).astype(q.dtype)

    return jax.vmap(per_seq)(q, block_tables, seq_lens)


# ---------------------------------------------------------------------------
# Pallas kernel: one KV block (all its tokens' heads) per grid step,
# table-driven DMA
# ---------------------------------------------------------------------------
def _paged_decode_kernel(lens_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale, block_size):
    # grid (batch, max_blocks): the index maps already steered this step's
    # k/v refs to block_tables[b, t] via scalar prefetch; the flash
    # (m, l, acc) state lives in VMEM scratch across the innermost t steps
    # (same recurrence as ops/flash_attention._fwd_kernel), one row per head.
    b = pl.program_id(0)
    t = pl.program_id(1)
    num_t = pl.num_programs(1)
    kv_len = lens_ref[b]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    @pl.when(t * block_size < kv_len)
    def _step():
        # one query row per head: these are matrix-vector products, so they
        # run on the VPU in f32 (exact products of the stored values, f32
        # accumulation) instead of padding a one-row operand onto the MXU
        q = q_ref[0].astype(jnp.float32)               # (h, d)
        k = k_ref[0].astype(jnp.float32)               # (bs, h, d)
        v = v_ref[0].astype(jnp.float32)
        # heads stay on the sublanes throughout (keepdims), so the
        # per-head statistics below meet the (h, d) accumulator without a
        # relayout; the block's tokens are the leading axis
        s = jnp.sum(q[None] * k, axis=2, keepdims=True) * scale  # (bs, h, 1)
        rows = t * block_size + lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(rows < kv_len, s, _NEG_INF)
        m_prev = m_scr[...]                            # (h, _LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.where(rows < kv_len, jnp.exp(s - m_new[:, :1]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=0)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.sum(p * v, axis=0)
        m_scr[...] = m_new

    @pl.when(t == num_t - 1)
    def _finalize():
        # kv_len == 0 (a padding row) never entered _step: l stays 0 and
        # the guarded divide returns zeros, matching the reference
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                           block_size: int,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """The table-driven Pallas kernel (interpret-mode off TPU).

    Blocks are ``(1, block_size, heads, head_dim)`` page tiles and
    ``(1, heads, head_dim)`` q/out tiles: the two minor dims of every
    block equal the array's, which is what Mosaic's tiling rule asks of a
    block narrower than (8, 128)."""
    from jax.experimental.pallas import tpu as pltpu
    _check_shapes(q, k_pages, v_pages, block_tables, seq_lens, block_size)
    b, h, d = q.shape
    max_blocks = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    page_spec = pl.BlockSpec((1, block_size, h, d),
                             lambda bi, ti, lens, tbl:
                             (tbl[bi, ti], 0, 0, 0))
    row_spec = pl.BlockSpec((1, h, d), lambda bi, ti, lens, tbl: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # seq_lens, block_tables
        grid=(b, max_blocks),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((h, _LANES), jnp.float32),   # m
            pltpu.VMEM((h, _LANES), jnp.float32),   # l
            pltpu.VMEM((h, d), jnp.float32),        # acc
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=float(scale),
                               block_size=int(block_size))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="paged_decode",
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(block_tables, jnp.int32), q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    block_size: int, scale: Optional[float] = None):
    """Ragged paged-attention decode for ``q`` of shape
    ``(batch, heads, head_dim)`` (one query token per sequence).

    TPU backends take the Pallas kernel; everything else takes the lax
    reference (same numerics) so the CPU test mesh exercises the full
    serving path at XLA speed.  ``PTPU_PAGED_KERNEL`` forces a path.
    """
    forced = os.environ.get(PAGED_KERNEL_ENV, "").strip().lower()
    if forced in ("pallas", "kernel", "1"):
        return paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                      seq_lens, block_size, scale)
    if forced in ("reference", "lax", "0"):
        return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                         seq_lens, block_size, scale)
    if jax.default_backend() == "tpu":
        return paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                      seq_lens, block_size, scale)
    return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     seq_lens, block_size, scale)
