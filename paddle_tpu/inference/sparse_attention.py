"""Learned sparse attention over latent pages (ISSUE 32): DeepSeek sparse
attention as the DeepSeek-V3.2-Exp report defines it, which GLM-5 uses.

A layer with an indexer keeps TWO page arrays under one block table: the
latent rows of ``latent_attention`` and, a token, one narrow index key.
A decode step then runs, a layer,

    I[b, s]  = sum_j w[b, j] ReLU(q_idx[b, j] . k_idx[b][s])      s < len[b]
    S_b      = the min(top_k, len[b]) positions of largest I[b, .]
    out[b]   = softmax(q[b] . rows[S_b]^T * scale) . rows[S_b][:, :value_width]

- :func:`dsa_index_scores` — the kernel ``dsa_index_scores``: grid ``(rows,
  waves)``, a wave being ``pages_per_step`` table entries, with the lengths
  and the block table as scalar prefetch (as ``mla_latent_attn`` has them).
  The page array is handed to the call once a wave slot, each slot with its
  own index map, so the pipeline fetches a wave's pages side by side;
  entries past a row's last page are clamped onto that page (an unchanged
  block index is not fetched again) and a wave wholly past the row's length
  writes minus infinity and computes nothing: the work follows the LIVE
  pages, the grid's fixed cost the table's width over ``pages_per_step``.
  Scores are float32, ``(rows, max context)``, minus infinity past a row's
  length.
- :func:`dsa_select` — the exact selection: ``lax.top_k`` (never
  ``approx_max_k``), equal scores to the lower position; positions that do
  not exist come back as -1.  :func:`dsa_slots` turns positions into pool
  slots through the block table.
- :func:`dsa_sparse_attn` — the absorbed form over the selected rows only.
  The rows arrive by an XLA gather (``(rows, top_k, width)``, contiguous),
  and the kernel ``dsa_sparse_attn`` is the latent kernel's body walking
  them in chunks: row DMAs inside a kernel would be ``top_k`` copies of
  1,280 bytes a row a layer, which the DMA engine's issue rate bounds far
  below the gather (PERF.md section 6, PR 32).
- ``*_reference`` — the ``jax.numpy`` float32 ``HIGHEST`` twin of each: the
  route off the TPU and the kernels' oracle, as
  ``latent_attention_reference`` is.
- :func:`dsa_prefill_mask` and :func:`blocked_attention` — a chunk on
  itself, in blocks of queries: index scores of a block against the keys it
  can see, the exact ``top_k``-th score as the block's threshold (ties to
  the lower position), then masked plain attention a group of heads at a
  time (``models/latent_decoder.LatentAttention`` holds the loop over the
  groups).  Without a mask ``blocked_attention`` is plain causal attention
  in blocks, for chunks too long for ``latent_decoder._causal_attention``.

The three attention kernels of a latent model: ``mla_latent_attn``
(``latent_attention.py``: every live page), ``dsa_index_scores`` and
``dsa_sparse_attn`` (here: scores over every live index key, attention over
the selected rows).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..framework.errors import enforce
from ..ops.flash_attention import _interpret
from .latent_attention import (latent_attention_pallas,
                               latent_attention_reference)

__all__ = ["dsa_index_scores", "dsa_index_scores_pallas",
           "dsa_index_scores_reference", "dsa_select", "dsa_slots",
           "dsa_sparse_attn", "dsa_sparse_attn_pallas",
           "dsa_sparse_attn_reference", "dsa_prefill_mask",
           "blocked_attention", "index_scores_dense", "select_mask",
           "query_block"]

_NEG = -jnp.inf


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -- index scores over the pages ----------------------------------------------
def _check_index(q, w, pages, block_tables, seq_lens):
    b, h, d = q.shape
    enforce(pages.ndim == 3 and pages.shape[2] == d,
            f"index pages {pages.shape} disagree with q {q.shape}")
    enforce(w.shape == (b, h), f"head weights {w.shape} for q {q.shape}")
    enforce(block_tables.shape[0] == b and seq_lens.shape == (b,),
            f"tables {block_tables.shape} / lens {seq_lens.shape} "
            f"disagree with batch {b}")


def dsa_index_scores_reference(q, w, pages, block_tables, seq_lens):
    """``q (rows, heads, dim)``, ``w (rows, heads)`` against ``pages
    (num_blocks, block_size, dim)``: float32 ``(rows, max context)``."""
    _check_index(q, w, pages, block_tables, seq_lens)
    max_ctx = block_tables.shape[1] * pages.shape[1]

    def per_seq(qb, wb, table, ln):
        keys = jnp.take(pages, table, axis=0).reshape(max_ctx, -1)
        dots = jnp.einsum("hd,sd->hs", qb.astype(jnp.float32),
                          keys.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
        score = jnp.einsum("hs,h->s", jnp.maximum(dots, 0.0),
                           wb.astype(jnp.float32),
                           precision=lax.Precision.HIGHEST)
        return jnp.where(jnp.arange(max_ctx) < ln, score, _NEG)

    return jax.vmap(per_seq)(q, w, block_tables, seq_lens)


def _index_kernel(lens_ref, table_ref, q_ref, w_ref, *refs, block_size,
                  pages_per_step):
    del table_ref
    page_refs, o_ref = refs[:pages_per_step], refs[pages_per_step]
    b, t = pl.program_id(0), pl.program_id(1)
    ln = lens_ref[b]
    base = t * pages_per_step * block_size

    @pl.when(base >= ln)
    def _dead():
        o_ref[...] = jnp.full(o_ref.shape, _NEG, o_ref.dtype)

    @pl.when(base < ln)
    def _live():
        q = q_ref[0]                                     # (heads, dim)
        w = w_ref[0]                                     # (heads, 1) f32
        for j, page in enumerate(page_refs):
            dots = lax.dot_general(q, page[0], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            score = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0,
                            keepdims=True)               # (1, block)
            cols = base + j * block_size + lax.broadcasted_iota(
                jnp.int32, score.shape, 1)
            o_ref[0, :, j * block_size:(j + 1) * block_size] = jnp.where(
                cols < ln, score, _NEG)


@functools.partial(jax.jit, static_argnames=("pages_per_step", "interpret"))
def dsa_index_scores_pallas(q, w, pages, block_tables, seq_lens,
                            pages_per_step: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Jitted, so a step program lowers it once for all layers."""
    from jax.experimental.pallas import tpu as pltpu
    _check_index(q, w, pages, block_tables, seq_lens)
    b, h, d = q.shape
    block_size = pages.shape[1]
    max_blocks = block_tables.shape[1]
    if pages_per_step is None:           # about 2,048 tokens a grid step
        pages_per_step = max(1, min(max_blocks, 2048 // block_size))
    p = pages_per_step
    waves = -(-max_blocks // p)

    def page_index(j):
        def index(bi, ti, lens, tbl):
            last = jnp.maximum(
                (lens[bi] + block_size - 1) // block_size - 1, 0)
            return (tbl[bi, jnp.minimum(ti * p + j, last)], 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # seq_lens, block_tables
        grid=(b, waves),
        in_specs=[pl.BlockSpec((1, h, d), lambda bi, ti, lens, tbl:
                               (bi, 0, 0)),
                  pl.BlockSpec((1, h, 1), lambda bi, ti, lens, tbl:
                               (bi, 0, 0))]
        + [pl.BlockSpec((1, block_size, d), page_index(j))
           for j in range(p)],
        out_specs=pl.BlockSpec((1, 1, p * block_size),
                               lambda bi, ti, lens, tbl: (bi, 0, ti)),
    )
    kernel = functools.partial(_index_kernel, block_size=block_size,
                               pages_per_step=p)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, waves * p * block_size),
                                       jnp.float32),
        name="dsa_index_scores",
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(seq_lens, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      q, w.astype(jnp.float32)[..., None], *([pages] * p))
    return out[:, 0, :max_blocks * block_size]


def dsa_index_scores(q, w, pages, block_tables, seq_lens):
    """The kernel on a TPU, the reference elsewhere."""
    if _on_tpu():
        return dsa_index_scores_pallas(q, w, pages, block_tables, seq_lens)
    return dsa_index_scores_reference(q, w, pages, block_tables, seq_lens)


# -- the selection --------------------------------------------------------------
def dsa_select(scores, top_k: int):
    """``scores (rows, context)`` float32, minus infinity where a row may
    not look -> ``(positions (rows, top_k) int32, count (rows,))``: the
    positions of the ``top_k`` largest scores, largest first, equal scores
    to the lower position; -1 where the row has fewer."""
    rows, ctx = scores.shape
    # minus zero as zero: equal scores are equal whatever their sign bit
    vals, idx = lax.top_k(jnp.where(scores == 0, 0.0, scores),
                          min(int(top_k), ctx))
    pos = jnp.where(vals > _NEG, idx.astype(jnp.int32), -1)
    if ctx < top_k:
        pos = jnp.pad(pos, ((0, 0), (0, top_k - ctx)), constant_values=-1)
    return pos, jnp.sum(pos >= 0, axis=1).astype(jnp.int32)


def dsa_slots(positions, block_tables, block_size: int):
    """Pool slots of ``positions (rows, top_k)`` through the block table
    (slot 0 where the position is -1: masked by the count)."""
    p = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(block_tables, p // block_size, axis=1)
    return jnp.where(positions >= 0, blk * block_size + p % block_size, 0)


# -- attention over the selected rows --------------------------------------------
def _gathered(pages, slots):
    return jnp.take(pages.reshape(-1, pages.shape[-1]), slots, axis=0)


def _chunk(top_k: int) -> int:
    for c in (512, 256, 128):
        if top_k % c == 0:
            return c
    return top_k


def _as_pages(rows):
    """``rows (b, top_k, width)`` as the latent kernel's pages and table."""
    b, k, w = rows.shape
    c = _chunk(k)
    table = jnp.arange(b * (k // c), dtype=jnp.int32).reshape(b, k // c)
    return rows.reshape(b * (k // c), c, w), table


def dsa_sparse_attn_reference(q, pages, slots, counts, value_width: int,
                              scale: float):
    """``q (rows, heads, width)`` over the ``counts[b]`` first of the pool
    slots ``slots (rows, top_k)`` of ``pages``: ``(rows, heads,
    value_width)``."""
    rows, table = _as_pages(_gathered(pages, slots))
    return latent_attention_reference(q, rows, table, counts, value_width,
                                      scale)


@functools.partial(jax.jit,
                   static_argnames=("value_width", "scale", "interpret"))
def dsa_sparse_attn_pallas(q, pages, slots, counts, value_width: int,
                           scale: float, interpret: Optional[bool] = None):
    rows, table = _as_pages(_gathered(pages, slots))
    return latent_attention_pallas(q, rows, table, counts, value_width,
                                   scale, interpret=interpret,
                                   name="dsa_sparse_attn")


def dsa_sparse_attn(q, pages, slots, counts, value_width: int, scale: float):
    if _on_tpu():
        return dsa_sparse_attn_pallas(q, pages, slots, counts,
                                      value_width=int(value_width),
                                      scale=float(scale))
    return dsa_sparse_attn_reference(q, pages, slots, counts, value_width,
                                     scale)


# -- prefill: a chunk on itself, in blocks of queries ----------------------------
def index_scores_dense(q_i, k_i, w_i, group: int = 16):
    """``I (keys, queries)`` float32 for ``q_i (queries, heads, dim)``,
    ``k_i (keys, dim)``, ``w_i (queries, heads)``; no mask.  Keys lead:
    what follows reduces over them, and a reduction over the second-minor
    axis is adds of whole registers where one over the minor axis crosses
    lanes.  The index heads go through ``group`` at a time, so the
    ``(heads, keys, queries)`` products of a block never all stand at
    once."""
    t, h, _ = q_i.shape
    group = min(group, h)
    while h % group:
        group -= 1

    def part(acc, qw):
        q, w = qw                                   # (t, group, d), (t, group)
        dots = jnp.einsum("thd,sd->hst", q, k_i,
                          preferred_element_type=jnp.float32)
        return acc + jnp.einsum("hst,th->st", jnp.maximum(dots, 0.0),
                                w.astype(jnp.float32)), None

    split = lambda a: jnp.moveaxis(
        a.reshape((t, h // group, group) + a.shape[2:]), 1, 0)
    acc, _ = lax.scan(part, jnp.zeros((k_i.shape[0], t), jnp.float32),
                      (split(q_i), split(w_i)))
    return acc


def _ordered_bits(scores):
    """float32 -> uint32 keys in the same order (minus zero as zero)."""
    u = lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def select_mask(scores, top_k: int):
    """The ``top_k`` largest of each COLUMN of ``scores (keys, queries)``
    as a mask, equal scores to the lower position; entries at minus
    infinity are never selected.  Exact, and no sort: a column's
    ``top_k``-th largest score is built a bit at a time from counts (32
    passes of compare-and-sum over the block: 0.25 ms for 128 x 16,384 on
    a v5e where ``lax.top_k`` takes 1.2 ms; my chip run, PR 32)."""
    s, q = scores.shape
    seen = scores > _NEG
    if s <= top_k:
        return seen
    keys = _ordered_bits(scores)

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand, axis=0, keepdims=True) >= top_k
        return jnp.where(enough, cand, thr)

    thr = lax.fori_loop(0, 32, bit, jnp.zeros((1, q), jnp.uint32))
    above, tied = keys > thr, keys == thr
    need = top_k - jnp.sum(above, axis=0, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=0) <= need))) & seen


def query_block(s: int) -> int:
    """Queries a block of a chunk of ``s`` tokens: 128, one lane tile
    (the float32 scores of 16 heads x 16,384 keys x 128 queries are 128
    MiB), or the largest power of two under it that divides ``s``."""
    block = 128
    while s % block:
        block //= 2
    return max(block, 1)


def _segments(s: int, block: int):
    """The blocks of a chunk in up to four runs, each with the number of
    keys its last query can see: 5/8 of the square's work at four."""
    blocks = s // block
    runs = max(1, min(4, blocks))
    while blocks % runs:
        runs -= 1
    per = blocks // runs
    return [(g * per, per, (g + 1) * per * block) for g in range(runs)]


def _causal_t(start, block, span, ln):
    """``(keys, queries)``: key at or before the query, and real."""
    at = start + jnp.arange(block)
    rows = jnp.arange(span)
    return (rows[:, None] <= at[None, :]) & (rows[:, None] < ln)


def _live_blocks(ln, block: int, first: int, per: int):
    """How many of the blocks ``first .. first + per`` hold a real query:
    a chunk is padded to its bucket, and the loops below follow the
    sequence's own length, not the bucket's."""
    return jnp.clip(-(-ln // block) - first, 0, per)


def dsa_prefill_mask(index, ln, last, top_k: int, block: int):
    """Which keys each query of a chunk attends: ``index = (q_i (s, index
    heads, dim), k_i (s, dim), w_i (s, index heads))``, keys at or past
    ``ln`` padding.  A block of queries at a time, and only the blocks
    that hold a real query: index scores against the keys the block can
    see, the exact ``top_k``-th score as the threshold, equal scores to
    the lower position.  Returns ``(mask (keys, queries) bool, selected
    (top_k,))``: the positions the query at ``last`` attends, largest
    score first, -1 where none."""
    q_i, k_i, w_i = index
    s = k_i.shape[0]
    mask = jnp.zeros((s, s), bool)
    col = jnp.full((s,), _NEG, jnp.float32)
    for first, per, span in _segments(s, block):
        def one(j, carry, first=first, span=span):
            mask, col = carry
            start = (first + j) * block
            cut = lambda a: lax.dynamic_slice_in_dim(a, start, block)
            with jax.named_scope("dsa.index_scores"):
                score = jnp.where(
                    _causal_t(start, block, span, ln),
                    index_scores_dense(cut(q_i), k_i[:span], cut(w_i)), _NEG)
            with jax.named_scope("dsa.select"):
                keep = select_mask(score, top_k)
            mine = jnp.pad(score[:, jnp.clip(last - start, 0, block - 1)],
                           (0, s - span), constant_values=_NEG)
            here = (last >= start) & (last < start + block)
            return (lax.dynamic_update_slice(
                mask, jnp.pad(keep, ((0, s - span), (0, 0))), (0, start)),
                jnp.where(here, mine, col))

        mask, col = lax.fori_loop(0, _live_blocks(ln, block, first, per),
                                  one, (mask, col))
    with jax.named_scope("dsa.select"):
        pos, _ = dsa_select(col[None], top_k)
    return mask, pos[0]


def blocked_attention(q, k, v, ln, scale: float, block: int, mask=None):
    """Some heads of one sequence on itself, a block of queries at a
    time and only the blocks that hold a real query (a padding query's
    output stays zero): ``q, k (s, heads, d)``, ``v (s, heads, dv)``, keys
    at or past ``ln`` padding; under ``mask (keys, queries)`` where given
    (the indexer's selection), else causal.  The scores lie ``(heads,
    keys, queries)``, so the softmax reduces over the second-minor axis (a
    block of 256 queries reduced over the minor axis took 4 times the
    time of two blocks of 128; my chip run, PR 32)."""
    s = k.shape[0]
    out = jnp.zeros((s,) + v.shape[1:], v.dtype)
    for first, per, span in _segments(s, block):
        def one(j, out, first=first, span=span):
            start = (first + j) * block
            seen = (_causal_t(start, block, span, ln) if mask is None
                    else lax.dynamic_slice(mask, (0, start), (span, block)))
            sc = jnp.einsum("qhd,khd->hkq",
                            lax.dynamic_slice_in_dim(q, start, block),
                            k[:span],
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(seen[None], sc, -1e30)
            p = jnp.exp(sc - jnp.max(sc, axis=1, keepdims=True))
            o = jnp.einsum("hkq,khd->qhd", p.astype(v.dtype), v[:span],
                           preferred_element_type=jnp.float32)
            o = (o / jnp.sum(p, axis=1).T[:, :, None]).astype(v.dtype)
            return lax.dynamic_update_slice_in_dim(out, o, start, 0)

        out = lax.fori_loop(0, _live_blocks(ln, block, first, per), one, out)
    return out
