"""Grouped-query attention over paged keys and values of unlike widths, with
a window and a learned sink where the layer has them (ISSUE 35).

``heads`` query heads of ``dk`` values share ``n_kv`` key/value heads:
query head ``j`` reads key/value head ``j // (heads / n_kv)``.  Keys are
``dk`` wide and values ``dv`` (192 and 128 in MiMo-V2-Flash).  A window
layer's query at position ``i`` attends ``j <= i`` with ``i - j < window``,
and may have one learned logit a query head, the **sink**, which takes
weight in the softmax and gives no value:

    p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))

A token keeps one FLAT row of keys and one of values a layer, ``(n_kv *
dk,)`` and ``(n_kv * dv,)``: a page ``(block_size, n_kv * dk)`` is whole
tiles whatever ``n_kv`` is (a ``(4, 192)`` slab a token would be stored as
``(16, 256)``, 5.3 times the bytes).  The heads are not cut out of the flat
row: the query is spread into ``(heads, n_kv * dk)`` with head ``j``'s
values in its group's columns and zeros elsewhere, so ONE matrix product
against the page as it lies gives every head's scores against its own
key head, and the heads of a group share each page read.  ``p @ v`` gives
``(heads, n_kv * dv)``, of which head ``j`` keeps its group's ``dv``
columns.  That is ``n_kv`` times the needed FLOPs on an MXU that a decode
step leaves idle anyway (the kernel is bound by the page reads).

- :func:`gqa_decode_pallas` — the decode kernel, ``gqa_full_decode`` or
  ``gqa_window_decode`` in a trace.  One program walks the rows that hold
  anything and, of each, the LIVE blocks only, a wave of pages a DMA round
  into double-buffered VMEM (the structure of ``paged_attention``'s
  kernel): of a full layer blocks ``0 .. cdiv(len, block_size)``, of a
  window layer the blocks that hold positions ``len - window .. len``.
  The block table is read as a ring, block ``b`` at column ``b % width``:
  a full layer's table is wider than any sequence, so that is column
  ``b``; a window layer's is ``kv_cache.window_table_width`` wide and
  holds nothing else (``kv_cache.py``).  The sink is the softmax state's
  start: ``m = sink, l = 1, acc = 0``.
- :func:`gqa_decode_reference` — gather + masked softmax, float32 at
  ``HIGHEST``: the route off the TPU and the kernel's oracle.
- :func:`gqa_prefill_attention` — a chunk on itself, in XLA, a block of
  queries at a time and only the blocks that hold a real query.  A full
  layer's block attends the keys up to its own end; a window layer's
  attends a BAND, its own block of keys and as many before it as the
  window reaches, so the work is ``chunk x band`` and not ``chunk x
  chunk`` (at 8,192 tokens and a window of 128, a sixty-fourth).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..framework.errors import enforce
from ..ops.flash_attention import _dot, _interpret, _NEG_INF

__all__ = ["gqa_decode", "gqa_decode_pallas", "gqa_decode_reference",
           "gqa_prefill_attention", "spread_heads"]

_WAVE_VMEM_BYTES = 6 << 20      # K and V waves, each double buffered
_VMEM_LIMIT = 64 << 20          # q and the output are whole in VMEM
_BAND_CHUNK = 8                 # query blocks a pass of the banded prefill


def _check(q, k_pages, v_pages, block_tables, seq_lens, n_kv):
    b, h, dk = q.shape
    enforce(k_pages.ndim == 3 and v_pages.ndim == 3
            and k_pages.shape[:2] == v_pages.shape[:2]
            and k_pages.shape[2] == n_kv * dk
            and v_pages.shape[2] % n_kv == 0 and h % n_kv == 0,
            f"pages k={k_pages.shape} v={v_pages.shape} disagree with q "
            f"{q.shape} over {n_kv} key/value heads")
    enforce(block_tables.shape[0] == b and seq_lens.shape == (b,),
            f"tables {block_tables.shape} / lens {seq_lens.shape} "
            f"disagree with batch {b}")


def spread_heads(heads: int, n_kv: int, dk: int, dtype):
    """``(expand (dk, n_kv * dk), keep (heads, n_kv * dk))``: ``(q @
    expand) * keep`` is ``q (heads, dk)`` with head ``j``'s values in the
    columns of key/value head ``j // (heads / n_kv)`` and zeros
    elsewhere."""
    expand = jnp.tile(jnp.eye(dk, dtype=dtype), (1, n_kv))
    group = jnp.arange(heads)[:, None] // (heads // n_kv)
    keep = (jnp.arange(n_kv * dk)[None, :] // dk == group)
    return expand, keep.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Reference: gather + masked softmax (the CPU serving path and the oracle)
# ---------------------------------------------------------------------------
def gqa_decode_reference(q, k_pages, v_pages, block_tables, seq_lens,
                         n_kv: int, scale: float,
                         window: Optional[int] = None, sink=None):
    """``q (batch, heads, dk)`` against ``k_pages (blocks, block_size,
    n_kv * dk)`` / ``v_pages (.., n_kv * dv)``: ``(batch, heads, dv)``.  A
    row with ``seq_lens[b] == 0`` (decode-batch padding) returns zeros."""
    _check(q, k_pages, v_pages, block_tables, seq_lens, n_kv)
    (_, h, dk), bs = q.shape, k_pages.shape[1]
    dv = v_pages.shape[2] // n_kv
    width = block_tables.shape[1]
    reach = width * bs
    sink = None if sink is None else sink.astype(jnp.float32)

    def per_seq(qb, table, ln):
        k = jnp.take(k_pages, table, axis=0).reshape(reach, n_kv, dk)
        v = jnp.take(v_pages, table, axis=0).reshape(reach, n_kv, dv)
        # column c of the ring holds the one block b = c (mod width) among
        # the last `width` of the sequence's blocks
        hi = -(-ln // bs)
        col = jnp.arange(width)
        block = hi - 1 - jnp.mod(hi - 1 - col, width)
        pos = (block[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        valid = (pos >= 0) & (pos < ln)
        if window is not None:
            valid &= pos >= ln - window
        qg = qb.astype(jnp.float32).reshape(n_kv, h // n_kv, dk)
        s = jnp.einsum("gjd,lgd->gjl", qg, k.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST) * scale
        s = jnp.where(valid[None, None], s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink.reshape(n_kv, -1, 1))
        p = jnp.where(valid[None, None], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if sink is not None:
            l = l + jnp.exp(sink.reshape(n_kv, -1, 1) - m)
        # a column the row does not hold may be anyone's: 0 * NaN is NaN
        v = jnp.where(valid[:, None, None], v, 0)
        out = jnp.einsum("gjl,lgd->gjd", p, v.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        return (out / jnp.maximum(l, 1e-30)).reshape(h, dv).astype(q.dtype)

    return jax.vmap(per_seq)(q, block_tables, seq_lens)


# ---------------------------------------------------------------------------
# Pallas kernel: one program walks the live rows' live pages, a wave of
# pages per DMA round
# ---------------------------------------------------------------------------
def _pages_per_wave(k_pages, v_pages, width: int) -> int:
    page = (k_pages.shape[1] * (k_pages.shape[2] + v_pages.shape[2])
            * jnp.dtype(k_pages.dtype).itemsize)
    return max(1, min(_WAVE_VMEM_BYTES // (2 * page), width, 8))


def _gqa_decode_kernel(lens_ref, table_ref, q_ref, expand_ref, keep_ref,
                       sink_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                       live_ref, *, scale, block_size, wave, window, n_kv,
                       has_sink):
    from jax.experimental.pallas import tpu as pltpu
    batch, h, _ = q_ref.shape
    width = table_ref.shape[1]
    dv = v_buf.shape[2] // n_kv
    tokens = wave * block_size                  # tokens a wave holds

    def first_block(row):
        if window is None:
            return jnp.int32(0)
        return jnp.maximum(lens_ref[row] - window, 0) // block_size

    def wave_copies(row, w, slot, go):
        # the pages of wave ``w`` among row ``row``'s live ones, K and V
        # of each into the slot's buffers; ``go`` starts or awaits them
        at = first_block(row) + w * wave
        pages = pl.cdiv(lens_ref[row], block_size) - at

        def one(j, _):
            page = table_ref[row, lax.rem(at + j, width)]
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
    col = lax.broadcasted_iota(jnp.int32, (h, tokens), 1)
    buf_row = lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)
    m0 = (sink_ref[...][:, :1] if has_sink
          else jnp.full((h, 1), _NEG_INF, jnp.float32))
    l0 = jnp.full((h, 1), 1.0 if has_sink else 0.0, jnp.float32)

    @pl.when(n_live > 0)
    def _first():
        start(live_ref[0], 0, 0)

    def row_body(i, slot):
        b = live_ref[i]
        kv_len = lens_ref[b]
        base = first_block(b) * block_size       # position of buffer row 0
        n_waves = pl.cdiv(kv_len - base, tokens)
        # head j's values in its key/value head's columns, zeros elsewhere
        q = (_dot(q_ref[b], expand_ref[...], (((1,), (0,)), ((), ())))
             * keep_ref[...]).astype(k_buf.dtype)

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
            k, v = k_buf[slot], v_buf[slot]
            # the buffers' rows past the row's last token hold whatever an
            # earlier wave left there: a stale score is replaced below,
            # and a stale value is zeroed here, because 0 * NaN is NaN
            at = base + w * tokens
            v = jnp.where(buf_row < kv_len - at, v, jnp.zeros_like(v))
            s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
            valid = col < kv_len - at
            if window is not None:
                valid &= col >= kv_len - window - at
            s = jnp.where(valid, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + _dot(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())))
            return 1 - slot, m_new, l_new, acc

        slot, _, l, acc = lax.fori_loop(
            0, n_waves, wave_body,
            (slot, m0, l0, jnp.zeros((h, n_kv * dv), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)
        per = h // n_kv          # head j keeps its own group's columns
        o_ref[b] = jnp.concatenate(
            [out[g * per:(g + 1) * per, g * dv:(g + 1) * dv]
             for g in range(n_kv)], axis=0).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, n_live, row_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("n_kv", "scale", "window",
                                             "interpret"))
def gqa_decode_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                      n_kv: int, scale: float,
                      window: Optional[int] = None, sink=None,
                      interpret: Optional[bool] = None):
    """The kernel (interpret-mode off TPU): one program, the pages in HBM,
    ``q`` and the output whole in VMEM.  Jitted so that a step program
    traces and lowers it once for all its layers of a kind."""
    from jax.experimental.pallas import tpu as pltpu
    _check(q, k_pages, v_pages, block_tables, seq_lens, n_kv)
    (b, h, dk), bs = q.shape, k_pages.shape[1]
    dv = v_pages.shape[2] // n_kv
    if interpret is None:
        interpret = _interpret()
    wave = _pages_per_wave(k_pages, v_pages, block_tables.shape[1])
    tokens = wave * bs
    expand, keep = spread_heads(h, n_kv, dk, q.dtype)
    sink_in = (jnp.zeros((h, 128), jnp.float32) if sink is None else
               jnp.broadcast_to(sink.astype(jnp.float32)[:, None], (h, 128)))
    whole = lambda a: pl.BlockSpec(                       # noqa: E731
        a.shape, lambda i, lens, tbl: (0,) * a.ndim)
    pages_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = jax.ShapeDtypeStruct((b, h, dv), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # seq_lens, block_tables
        grid=(1,),
        in_specs=[whole(q), whole(expand), whole(keep), whole(sink_in),
                  pages_spec, pages_spec],
        out_specs=whole(out_shape),
        scratch_shapes=[
            pltpu.VMEM((2, tokens, n_kv * dk), k_pages.dtype),   # K waves
            pltpu.VMEM((2, tokens, n_kv * dv), v_pages.dtype),   # V waves
            pltpu.SemaphoreType.DMA((2,)),                       # one a slot
            pltpu.SMEM((b,), jnp.int32),                         # live rows
        ],
    )
    kernel = functools.partial(
        _gqa_decode_kernel, scale=scale, block_size=bs, wave=wave,
        window=window, n_kv=n_kv, has_sink=sink is not None)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        name="gqa_full_decode" if window is None else "gqa_window_decode",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(seq_lens, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      q, expand, keep, sink_in, k_pages, v_pages)


def gqa_decode(q, k_pages, v_pages, block_tables, seq_lens, n_kv: int,
               scale: float, window: Optional[int] = None, sink=None):
    """The kernel on a TPU, the reference elsewhere (interpret-mode
    Pallas is orders slower than XLA on the CPU)."""
    impl = (gqa_decode_pallas if jax.default_backend() == "tpu"
            else gqa_decode_reference)
    return impl(q, k_pages, v_pages, block_tables, seq_lens, n_kv, scale,
                window, sink)


# ---------------------------------------------------------------------------
# Prefill: a chunk on itself, in blocks of queries
# ---------------------------------------------------------------------------
def _softmax_t(sc, seen, sink):
    """Probabilities over axis -2 of ``sc (.., keys, queries)`` where
    ``seen``, and their sums with the sink's weight in them: ``sink``
    broadcasts against ``sc`` with keys of 1."""
    sc = jnp.where(seen, sc, _NEG_INF)
    m = jnp.max(sc, axis=-2, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink)
    p = jnp.where(seen, jnp.exp(sc - m), 0.0)
    total = jnp.sum(p, axis=-2, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink - m)
    return p, jnp.maximum(total, 1e-30)


def _full_prefill(q, k, v, ln, scale, block, sink):
    """Causal attention, a key/value head at a time (its query heads share
    the keys), a block of queries at a time against the keys up to the
    block's end, in up to four runs of blocks (``sparse_attention``'s
    segments).  ``q (s, n_kv, per, dk)``, ``k (s, n_kv, dk)``."""
    from .sparse_attention import _causal_t, _live_blocks, _segments
    s, n_kv, per, _ = q.shape

    def group(args):
        qg, kg, vg, sg = args           # (s, per, dk), (s, dk), (s, dv)
        out = jnp.zeros((s, per, vg.shape[-1]), vg.dtype)
        for first, count, span in _segments(s, block):
            def one(j, out, first=first, span=span):
                start = (first + j) * block
                sc = jnp.einsum("qhd,kd->hkq",
                                lax.dynamic_slice_in_dim(qg, start, block),
                                kg[:span],
                                preferred_element_type=jnp.float32) * scale
                p, total = _softmax_t(
                    sc, _causal_t(start, block, span, ln)[None],
                    None if sink is None else sg[:, None, None])
                o = jnp.einsum("hkq,kd->qhd", (p / total).astype(vg.dtype),
                               vg[:span], preferred_element_type=jnp.float32)
                return lax.dynamic_update_slice_in_dim(
                    out, o.astype(vg.dtype), start, 0)

            out = lax.fori_loop(0, _live_blocks(ln, block, first, count),
                                one, out)
        return out

    sinks = (jnp.zeros((n_kv, per), jnp.float32) if sink is None
             else sink.astype(jnp.float32).reshape(n_kv, per))
    out = lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                          jnp.moveaxis(v, 1, 0), sinks))
    return jnp.moveaxis(out, 0, 1)                    # (s, n_kv, per, dv)


def _window_prefill(q, k, v, ln, scale, block, window, sink):
    """Banded attention: a block of queries against its own block of keys
    and the ``back`` blocks before it, ``_BAND_CHUNK`` blocks a pass and
    only the passes that hold a real query."""
    s, n_kv, per, dk = q.shape
    dv = v.shape[-1]
    nb = s // block
    back = -(-(window - 1) // block)
    band = (back + 1) * block
    chunk = min(_BAND_CHUNK, nb)
    while nb % chunk:
        chunk -= 1

    def banded(x):                     # (s, n_kv, d) -> (nb, band, n_kv, d)
        blocks = jnp.pad(x.reshape(nb, block, n_kv, -1),
                         ((back, 0), (0, 0), (0, 0), (0, 0)))
        return jnp.concatenate([blocks[j:j + nb] for j in range(back + 1)],
                               axis=1)

    def by_chunk(x):
        return x.reshape((nb // chunk, chunk) + x.shape[1:])

    sink_t = (None if sink is None else
              sink.astype(jnp.float32).reshape(1, n_kv, per, 1, 1))

    def attend(args):
        c, qc, kc, vc = args
        n = c * chunk + jnp.arange(chunk)
        qpos = n[:, None] * block + jnp.arange(block)[None, :]
        kpos = (n[:, None] - back) * block + jnp.arange(band)[None, :]
        seen = ((kpos[:, :, None] <= qpos[:, None, :])
                & (qpos[:, None, :] - kpos[:, :, None] < window)
                & (kpos[:, :, None] >= 0) & (kpos[:, :, None] < ln))
        sc = jnp.einsum("nqghd,nkgd->nghkq", qc, kc,
                        preferred_element_type=jnp.float32) * scale
        p, total = _softmax_t(sc, seen[:, None, None], sink_t)
        return jnp.einsum("nghkq,nkgd->nqghd", (p / total).astype(vc.dtype),
                          vc, preferred_element_type=jnp.float32
                          ).astype(vc.dtype)

    zeros = jnp.zeros((chunk, block, n_kv, per, dv), v.dtype)
    out = lax.map(
        lambda a: lax.cond(a[0] * chunk * block < ln, attend,
                           lambda _: zeros, a),
        (jnp.arange(nb // chunk), by_chunk(q.reshape(nb, block, n_kv, per,
                                                     dk)),
         by_chunk(banded(k)), by_chunk(banded(v))))
    return out.reshape(s, n_kv, per, dv)


def gqa_prefill_attention(q, k, v, ln, scale: float,
                          window: Optional[int] = None, sink=None,
                          block: Optional[int] = None):
    """One sequence's chunk on itself: ``q (s, heads, dk)``, ``k (s, n_kv,
    dk)``, ``v (s, n_kv, dv)`` -> ``(s, heads, dv)``; keys at or past
    ``ln`` are padding and a padding query's output is not to be read."""
    from .sparse_attention import query_block
    s, h, dk = q.shape
    n_kv = k.shape[1]
    block = query_block(s) if block is None else block
    enforce(s % block == 0 and h % n_kv == 0,
            f"chunk {s} in blocks of {block}, {h} heads over {n_kv}")
    q = q.reshape(s, n_kv, h // n_kv, dk)
    out = (_full_prefill(q, k, v, ln, scale, block, sink) if window is None
           else _window_prefill(q, k, v, ln, scale, block, window, sink))
    return out.reshape(s, h, -1)
