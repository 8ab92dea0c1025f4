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

- :func:`paged_attention_pallas` — the kernel, whose work follows the
  pages that are live and not the table's capacity.  Pages are
  token-major, ``(num_blocks, block_size, heads, head_dim)``
  (``inference/kv_cache.py``): one token's ``(heads, head_dim)`` slab is
  the minor tile, which is what lets the step program scatter new tokens
  into the pool in place, and a page is contiguous in HBM.  The page
  arrays stay in HBM; ``q``, the output, the lengths and the block
  tables (scalar prefetch) are all the kernel is handed.  One program
  lists the rows with ``seq_lens[b] > 0`` (padding rows may sit
  anywhere; they cost one scalar step and read zeros) and walks each
  one's table in ``cdiv(seq_lens[b], pages_per_wave * block_size)``
  **waves**: a wave is ``pages_per_wave`` page copies of K and of V
  (``make_async_copy``) into one half of a double-buffered VMEM scratch,
  and the next wave — of this row or the first of the next live row — is
  in flight while this one is computed.  The wave's size comes from the
  page's bytes against a 2 MB budget (8 pages of 16 x 16 x 128 bf16: 128
  tokens).  A row's last wave copies only the pages the row has; table
  entries past that are never read.  Arithmetic: online softmax in f32
  over the stored values.  All heads' scores of a wave are ONE
  matrix product ``q @ k.T`` with the wave's ``(tokens * heads,
  head_dim)`` slabs as they lie: column ``c`` is token ``c // heads``
  under head ``c % heads``, and row ``r`` keeps the columns of its own
  head (the others are masked like tokens past the length, so their
  probabilities are exact zeros and ``p @ v`` needs no gather either).
  bf16 products are exact in the MXU's f32 accumulator; the f32
  probabilities enter ``p @ v`` as three bf16 terms that sum to them
  (:func:`_probs_dot`).  The rows of a wave's buffers past the row's
  last token hold what an earlier wave left there: their scores are
  replaced by the mask and their values are zeroed before ``p @ v``, so
  one row's NaN never reaches another.  Mosaic copies a page out of the
  pool only where a token's slab is whole tiles, so a model allocates
  its pages as :func:`page_token_shape` says (12 x 64 in bf16 is kept as
  16 x 128, 2.67 times the bytes a token, against two conversions of
  the whole pool a page array a step between XLA's layout of a 64-wide
  array and the kernel's); ``q`` is widened with zeros to match and the
  output cut back.
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
from ..ops.flash_attention import _dot, _interpret, _LANES, _NEG_INF

__all__ = ["paged_attention", "paged_attention_pallas",
           "paged_attention_reference", "page_token_shape"]

PAGED_KERNEL_ENV = "PTPU_PAGED_KERNEL"


def _check_shapes(q, k_pages, v_pages, block_tables, seq_lens,
                  block_size: int):
    b, h, d = q.shape
    enforce(k_pages.ndim == 4 and k_pages.shape == v_pages.shape
            and k_pages.dtype == v_pages.dtype,
            f"page mismatch: k={k_pages.shape} {k_pages.dtype} "
            f"v={v_pages.shape} {v_pages.dtype}")
    hp, dp = k_pages.shape[2:]
    enforce(k_pages.shape[1] == block_size and hp >= h and dp >= d,
            f"pages {k_pages.shape} disagree with q {q.shape} at "
            f"block_size {block_size}")
    enforce(block_tables.shape[0] == b and seq_lens.shape == (b,),
            f"tables {block_tables.shape} / lens {seq_lens.shape} "
            f"disagree with batch {b}")


def _whole_tiles(heads: int, head_dim: int, dtype):
    """``(heads, head_dim)`` rounded up to the tiles of ``dtype``."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (-(-heads // sublanes) * sublanes, -(-head_dim // _LANES) * _LANES)


def page_token_shape(heads: int, head_dim: int, dtype):
    """What a token keeps in a page array that :func:`paged_attention`
    will read.  The kernel copies pages out of the pool in HBM, which
    Mosaic allows only where a token's ``(heads, head_dim)`` slab is
    whole tiles, so where the kernel runs compiled the slab is rounded up
    (12 x 64 in bf16 is kept as 16 x 128) and the added heads and dims
    hold zeros.  The reference takes the shape as it is."""
    if not _takes_kernel() or _interpret():
        return heads, head_dim
    return _whole_tiles(heads, head_dim, dtype)


def _widened(q, k_pages):
    """``q`` with zero heads and dims up to the pages' token shape: the
    zeros change no product, and the caller cuts them off the output."""
    (_, h, d), (hp, dp) = q.shape, k_pages.shape[2:]
    if (h, d) == (hp, dp):
        return q
    return jnp.pad(q, ((0, 0), (0, hp - h), (0, dp - d)))


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
    (_, heads, dim), (h, d) = q.shape, k_pages.shape[2:]
    if scale is None:
        scale = dim ** -0.5
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

    return jax.vmap(per_seq)(_widened(q, k_pages), block_tables,
                             seq_lens)[:, :heads, :dim]


# ---------------------------------------------------------------------------
# Pallas kernel: one program walks the live rows' live pages, a wave of
# pages per DMA round
# ---------------------------------------------------------------------------
_WAVE_VMEM_BYTES = 2 << 20      # K and V waves, each double buffered


def _pages_per_wave(block_size: int, h: int, d: int, dtype,
                    max_blocks: int) -> int:
    """How many pages one DMA wave brings in: what fits the VMEM budget,
    at most the table's width."""
    page = block_size * h * d * jnp.dtype(dtype).itemsize
    return max(1, min(_WAVE_VMEM_BYTES // (4 * page), max_blocks))


def _paged_decode_kernel(lens_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, live_ref, *,
                         scale, block_size, wave):
    from jax.experimental.pallas import tpu as pltpu
    batch, h, d = q_ref.shape
    tokens = wave * block_size                  # tokens a wave holds
    cols = tokens * h

    def wave_copies(row, w, slot, go):
        # the pages of wave ``w`` that row ``row`` has, K and V of each
        # into the slot's buffers; ``go`` starts them or waits for them
        pages = pl.cdiv(lens_ref[row], block_size) - w * wave

        def one(j, _):
            page = table_ref[row, w * wave + j]
            dst = pl.ds(j * block_size, block_size)
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                go(pltpu.make_async_copy(hbm.at[page], buf.at[slot, dst],
                                         sems.at[slot]))
        lax.fori_loop(0, jnp.minimum(pages, wave), one, None)

    def start(row, w, slot):
        wave_copies(row, w, slot, lambda cp: cp.start())

    def wait(row, w, slot):
        wave_copies(row, w, slot, lambda cp: cp.wait())

    # the rows that hold anything, in order, wherever the padding rows sit
    def note(b, n):
        @pl.when(lens_ref[b] > 0)
        def _():
            live_ref[n] = b
        return n + (lens_ref[b] > 0).astype(jnp.int32)
    n_live = lax.fori_loop(0, batch, note, jnp.int32(0))

    o_ref[...] = jnp.zeros_like(o_ref)           # an empty row's output
    # all heads' products of a wave come from one matrix product each:
    # column c of q @ k.T is token c // h under head c % h, and only the
    # columns on row r's own head count
    col = lax.broadcasted_iota(jnp.int32, (h, cols), 1)
    own_head = lax.rem(col, h) == lax.broadcasted_iota(jnp.int32,
                                                       (h, cols), 0)
    slab_row = lax.broadcasted_iota(jnp.int32, (cols, 1), 0)

    @pl.when(n_live > 0)
    def _first():
        start(live_ref[0], 0, 0)

    def row_body(i, slot):
        b = live_ref[i]
        kv_len = lens_ref[b]
        n_waves = pl.cdiv(kv_len, tokens)
        q = q_ref[b]                                     # (h, d)

        def wave_body(w, carry):
            slot, m_prev, l_prev, acc = carry
            # the next wave, of this row or the next live one, flies
            # while this one is computed
            @pl.when(w + 1 < n_waves)
            def _():
                start(b, w + 1, 1 - slot)

            @pl.when((w + 1 == n_waves) & (i + 1 < n_live))
            def _():
                start(live_ref[jnp.minimum(i + 1, batch - 1)], 0, 1 - slot)
            wait(b, w, slot)
            k = k_buf[slot].reshape(cols, d)
            v = v_buf[slot].reshape(cols, d)
            # the buffers' rows past the row's last token hold whatever
            # an earlier wave left there, another row's or this page's
            # last owner's: a stale score is replaced below, and a stale
            # value is zeroed here, because 0 * NaN is NaN
            live = (kv_len - w * tokens) * h
            v = jnp.where(slab_row < live, v, jnp.zeros_like(v))
            # exact products of the stored values, f32 accumulation
            s = _dot(*_same_dtype(q, k), (((1,), (1,)), ((), ()))) * scale
            valid = own_head & (col < live)                    # (h, cols)
            s = jnp.where(valid, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + _probs_dot(p, v)
            return 1 - slot, m_new, l_new, acc

        slot, _, l, acc = lax.fori_loop(
            0, n_waves, wave_body,
            (slot, jnp.full((h, 1), _NEG_INF, jnp.float32),
             jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32)))
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, n_live, row_body, jnp.int32(0))


def _same_dtype(q, k):
    """``q`` and ``k`` as they are where their types agree (bf16 products
    are exact in the MXU's f32 accumulator), both in f32 where not."""
    if q.dtype == k.dtype:
        return q, k
    return q.astype(jnp.float32), k.astype(jnp.float32)


def _probs_dot(p, v):
    """``p @ v`` for f32 probabilities ``p`` with f32 accumulation.  The
    MXU multiplies bf16: over bf16 pages ``p`` goes in as three bf16
    terms that sum to it (8 + 8 + 8 mantissa bits), stacked so that ``v``
    is loaded once, and every product is exact in f32; other pages take
    the fp32 contraction."""
    if v.dtype != jnp.bfloat16:
        return _dot(p, v.astype(jnp.float32), (((1,), (0,)), ((), ())))
    h = p.shape[0]
    terms = []
    for _ in range(3):
        terms.append(p.astype(jnp.bfloat16))
        p = p - terms[-1].astype(jnp.float32)
    out = _dot(jnp.concatenate(terms, axis=0), v, (((1,), (0,)), ((), ())))
    return out[:h] + out[h:2 * h] + out[2 * h:]


@functools.partial(jax.jit,
                   static_argnames=("block_size", "scale", "interpret"))
def paged_attention_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                           block_size: int,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """The table-driven Pallas kernel (interpret-mode off TPU): one
    program, the pages in HBM, ``q`` and the output whole in VMEM.

    Jitted so that a step program traces and lowers the kernel once for
    all its layers, not once a layer (seconds of every start)."""
    from jax.experimental.pallas import tpu as pltpu
    _check_shapes(q, k_pages, v_pages, block_tables, seq_lens, block_size)
    (b, heads, dim), (h, d) = q.shape, k_pages.shape[2:]
    if scale is None:
        scale = dim ** -0.5
    if interpret is None:
        interpret = _interpret()
    enforce(interpret or (h, d) == _whole_tiles(h, d, k_pages.dtype),
            f"Mosaic cannot copy a page out of a pool whose token slab "
            f"{(h, d)} is not whole tiles: allocate the pages as "
            f"page_token_shape() gives them")
    q = _widened(q, k_pages)
    wave = _pages_per_wave(block_size, h, d, k_pages.dtype,
                           block_tables.shape[1])
    tokens = wave * block_size
    rows_spec = pl.BlockSpec((b, h, d), lambda i, lens, tbl: (0, 0, 0))
    pages_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # seq_lens, block_tables
        grid=(1,),
        in_specs=[rows_spec, pages_spec, pages_spec],
        out_specs=rows_spec,
        scratch_shapes=[
            pltpu.VMEM((2, tokens, h, d), k_pages.dtype),   # K waves
            pltpu.VMEM((2, tokens, h, d), v_pages.dtype),   # V waves
            pltpu.SemaphoreType.DMA((2,)),                  # one a slot
            pltpu.SMEM((b,), jnp.int32),                    # live rows
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               block_size=block_size, wave=wave)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="paged_decode",
        interpret=interpret,
    )(jnp.asarray(seq_lens, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      q, k_pages, v_pages)[:, :heads, :dim]


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------
def _takes_kernel() -> bool:
    """The route: the kernel on a TPU backend, the reference elsewhere,
    unless ``PTPU_PAGED_KERNEL`` names one."""
    forced = os.environ.get(PAGED_KERNEL_ENV, "").strip().lower()
    if forced in ("pallas", "kernel", "1"):
        return True
    if forced in ("reference", "lax", "0"):
        return False
    return jax.default_backend() == "tpu"


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    block_size: int, scale: Optional[float] = None):
    """Ragged paged-attention decode for ``q`` of shape
    ``(batch, heads, head_dim)`` (one query token per sequence) over
    pages of ``(num_blocks, block_size) + page_token_shape(heads,
    head_dim, dtype)``.

    TPU backends take the Pallas kernel; everything else takes the lax
    reference (same numerics) so the CPU test mesh exercises the full
    serving path at XLA speed.  ``PTPU_PAGED_KERNEL`` forces a path.
    """
    impl = (paged_attention_pallas if _takes_kernel()
            else paged_attention_reference)
    return impl(q, k_pages, v_pages, block_tables, seq_lens, block_size,
                scale)
