"""Flash attention: the fused FMHA Pallas kernel.

Semantic reference: operators/fused/fused_attention_op.cc:221-357 FMHA path
(`FMHARef`, fused/fmha_ref.h:58 — QK^T, scale, mask, softmax, dropout, PV),
the causal-mask fusion `fused_softmax_mask_upper_triangle_op.cu`, the
in-kernel Philox dropout seeds (fused_attention_op.cc:292-311), and the
decode-time CacheKV path (fused_attention_op.cc:235).  The reference
materializes the (S, S) probability matrix in HBM; this kernel never does —
online softmax over KV blocks keeps everything in VMEM (the whole point of a
TPU-native rewrite: HBM bandwidth is the bottleneck, SURVEY §7 hard-part 2).

Layout: q, k, v are (batch, heads, seq, head_dim), flattened to
(batch*heads, seq, head_dim) for the kernel.  The training kernels run on
(batch*heads, block, block) grids.  The forward keeps the flash (m, l, acc)
recurrence in VMEM scratch across a q block's kv blocks, in fp32, so
per-step residency is O(block) and sequence length is HBM-bound (S=65536
runs single-chip).  Backward is recompute-based (no probability tensor
saved): a dkdv kernel over KV blocks and a dq kernel over Q blocks, both
replaying p = exp(qk - lse) and accumulating into revisited f32 output
blocks.  Backward VMEM residency is O(block), so sequence length is bounded
by HBM, not the scoped-vmem limit.

Causal masking pays only where the diagonal crosses a block
(``_pair_class``): a pair wholly above the diagonal, or wholly past the real
key length, computes nothing, and its index maps repeat the block of the
nearest live step, so it fetches nothing either; a pair wholly on or under
the diagonal, and inside the key length, runs with no mask at all; only a
pair the diagonal or the key length cuts builds one.  So the causal kernels
do ~half the FLOPs of the full square, and most of their pairs no mask
work.

Attention-prob dropout runs IN-KERNEL (the reference's Philox-offset
trick, counter-based): the keep mask for element (bh, row, col) is a pure
hash of (seed, bh, row, col), so forward and the recompute backward
regenerate bit-identical masks with no mask tensor in HBM.  The dropout
mask applies to the PV accumulation only; the softmax normalizer (and the
saved lse) stay dropout-free, and the output is rescaled by 1/(1-p).

Ragged sequence lengths are auto-padded to a Mosaic-legal multiple; padded
KV columns are masked to -inf in every kernel, and padded Q rows are
sliced away from the output, so callers can pass any length.

On non-TPU backends the kernels run in interpret mode, so the CPU test
mesh exercises the same code paths (the hash dropout is plain integer
jnp, identical under interpret and Mosaic).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..framework.errors import enforce

_NEG_INF = -1e30

# Mosaic requires the last two block dims to be (multiple of 8, multiple of
# 128) or equal to the array dims, so per-row statistics (lse, delta) can't be
# 2D (bh, seq) blocks of shape (1, bq).  Like the upstream TPU flash kernel,
# they travel as (bh, seq, _LANES) with the value broadcast across the 128
# lanes; kernels slice lanes back down to the KV-block width elementwise.
_LANES = 128
# The training kernels trace two bodies a pair class (masked and not), and
# Mosaic keeps each body's (block_q, block_k) float32 temporaries apart: at
# blocks of 1024 in float32 with heads of 128, flash_bwd_dkdv plans 19.3 MB,
# past the 16 MB default scoped limit (a v5e core has 128 MiB of VMEM).
_VMEM_LIMIT = 64 << 20



def _dot(a, b, dimension_numbers):
    """``lax.dot_general`` with f32 accumulation and a Mosaic-legal precision.

    The global ``jax_default_matmul_precision`` (e.g. "highest") leaks into
    Pallas kernel traces, and Mosaic rejects fp32 contract precision on bf16
    operands ("Bad lhs type").  Pin the precision from the operand dtypes
    instead: the native MXU bf16 pass for bf16 inputs, exact fp32
    contraction for f32 inputs (the hw parity test holds fp32 to 2e-5).
    """
    prec = (lax.Precision.HIGHEST
            if (a.dtype == jnp.float32 and b.dtype == jnp.float32)
            else lax.Precision.DEFAULT)
    return lax.dot_general(a, b, dimension_numbers,
                           preferred_element_type=jnp.float32,
                           precision=prec)

def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _stat_tile(x, width):
    """Widen a (rows, _LANES) lane-broadcast statistic to (rows, width)."""
    if width <= _LANES:
        return x[:, :width]
    assert width % _LANES == 0, (width, _LANES)
    return jnp.tile(x, (1, width // _LANES))


def _block_sizes(seq_q: int, seq_k: int):
    # swept on v5e for the kernels that masked every pair (bh·S·d with
    # d=64, best-of-3 fwd+bwd; July figures, not re-measured since the
    # mask split): at S=2048, 512/512 = 13.9ms vs
    # 19.5ms for 1024 and 46ms for 128 (small blocks starve the MXU when
    # the contraction dim is only 64); at S>=4096 the longer grid favors
    # 1024/1024 (S=4096: 23.1 vs 25.6ms; S=8192: 30.1 vs 35.2ms).
    # Fall back to the largest power-of-two block that divides the sequence
    # so every multiple of 128 stays supported; the resulting widths are
    # always either <=128 or a multiple of _LANES, which _stat_tile needs.
    def pick(seq):
        cands = (1024, 512, 256, 128) if seq >= 4096 else (512, 256, 128)
        for b in cands:
            if seq % b == 0:
                return b
        return seq
    return pick(seq_q), pick(seq_k)


def _pad_to_legal(seq: int) -> int:
    """Smallest Mosaic-legal padded length >= seq: a multiple of 128, or
    for short sequences a multiple of 8 (full-array blocks are legal)."""
    if seq % 128 == 0:
        return seq
    if seq < 128:
        return -(-seq // 8) * 8
    return -(-seq // 128) * 128


# ---------------------------------------------------------------------------
# Counter-based dropout hash (the Philox-offset analog,
# fused_attention_op.cc:292-311): keep(bh,row,col) is a murmur3-fmix mix of
# (seed, bh, row, col) — stateless, so fwd and recompute-bwd agree exactly.
# ---------------------------------------------------------------------------
def _keep_mask(seed_u32, bh, rows, cols, dropout_p):
    x = (rows.astype(jnp.uint32) * np.uint32(0x85EBCA6B)
         ^ cols.astype(jnp.uint32) * np.uint32(0xC2B2AE35)
         ^ seed_u32
         ^ bh.astype(jnp.uint32) * np.uint32(0x9E3779B1))
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # top 24 bits as a uniform in [0, 1); route the cast through int32 —
    # Mosaic has no uint32->float32 lowering, and the value fits 24 bits
    u = ((x >> 8).astype(jnp.int32).astype(jnp.float32)
         * np.float32(1.0 / (1 << 24)))
    return u >= dropout_p


# ---------------------------------------------------------------------------
# Pair classes: where the causal diagonal and the real key length fall
# ---------------------------------------------------------------------------
def _pair_class(qi, kj, block_q, block_k, offset, kv_len, causal):
    """Where the pair (q block ``qi``, kv block ``kj``) lies against the
    causal diagonal and the real key length, as ``(live, masked)``.

    ``live``: some (row, col) of the pair attends: a col below ``kv_len``
    and, causal, on or under the diagonal of bottom-right alignment (row +
    ``offset`` >= col).  ``masked``: live, and some element does not
    attend: the diagonal crosses the pair, or it holds ``kv_len``.  A live
    pair that is not masked runs with no mask at all.  Pure: the indices
    and lengths are Python ints (``flash_pair_counts``) or int32 scalars
    read in a kernel."""
    first_col = kj * block_k
    last_col = first_col + block_k - 1
    live = first_col < kv_len
    cut = last_col >= kv_len
    if causal:
        live = live & ((qi + 1) * block_q - 1 + offset >= first_col)
        cut = cut | (qi * block_q + offset < last_col)
    return live, live & cut


def _cuts_length(kv_len, block_k) -> bool:
    """Whether some pair can hold the real key length inside it (a traced
    length always can); where none can, no mask compares a col with it."""
    return not isinstance(kv_len, int) or kv_len % block_k != 0


def _positions(qi, kj, block_q, block_k):
    """The (rows, cols) of a pair's (block_q, block_k) scores."""
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kj * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return rows, cols


def _mask_scores(s, rows, cols, *, kv_len, offset, causal, cut_len):
    """``s`` with every element that does not attend at -inf."""
    valid = cols < kv_len if cut_len else None
    if causal:
        below = rows + offset >= cols
        valid = below if valid is None else valid & below
    return jnp.where(valid, s, _NEG_INF)


def _by_class(live, masked, body, can_mask):
    """``body(mask)`` where ``live``: ``mask=True`` where the pair is also
    ``masked``, ``False`` where it lies wholly inside; nothing elsewhere.
    ``can_mask`` False (static) traces the unmasked body alone."""
    if not can_mask:
        pl.when(live)(lambda: body(False))
        return
    pl.when(live & masked)(lambda: body(True))
    pl.when(live & jnp.logical_not(masked))(lambda: body(False))


def _last_kv(qi, block_q, block_k, offset, kv_len, causal):
    """The last kv block that q block ``qi`` attends (0 where it attends
    none), for a kv-side index map to clamp to (``_pair_class``'s live
    pairs of a q block are kv blocks 0 to this one)."""
    last = jnp.maximum(kv_len - 1, 0) // block_k
    if causal:
        diag = jnp.maximum(qi * block_q + block_q - 1 + offset, 0) // block_k
        last = jnp.minimum(diag, last)
    return last


def _first_q(kj, block_q, block_k, offset, kv_len, causal, num_q):
    """The first q block that attends kv block ``kj`` (the last q block
    where none does), for a q-side index map to clamp to (``_pair_class``'s
    live pairs of a kv block are q blocks from this one on)."""
    if not causal:
        return 0
    first = jnp.minimum(jnp.maximum(kj * block_k - offset, 0) // block_q,
                        num_q - 1)
    return jnp.where(kj * block_k < kv_len, first, num_q - 1)


def _kv_map(block_q, block_k, offset, kv_len, causal):
    """A kv-side block's index map on a (bh, q block, kv block) grid: a
    step past the q block's last live kv block repeats that block's index,
    so its DMA is not issued."""
    def kv_map(b, i, j):
        return b, jnp.minimum(
            j, _last_kv(i, block_q, block_k, offset, kv_len, causal)), 0
    return kv_map


def _q_map(block_q, block_k, offset, kv_len, causal, num_q):
    """A q-side block's index map on a (bh, kv block, q block) grid: a
    step before the kv block's first live q block takes that block's
    index, so the steps above the diagonal fetch only what the first live
    one needs."""
    def q_map(b, j, i):
        return b, jnp.maximum(i, _first_q(j, block_q, block_k, offset,
                                          kv_len, causal, num_q)), 0
    return q_map


def flash_pair_counts(seq_q: int, seq_k: int, causal: bool = True):
    """The training kernels' (q block, kv block) pairs of one batch*head
    by class, for ``flash_attention`` over these lengths: ``skipped`` (no
    element attends: no compute, no fetch), ``interior`` (every element
    attends: no mask) and ``diagonal`` (the diagonal or the key length cuts
    it: the mask is built).  Static per shape."""
    sq, sk = _pad_to_legal(seq_q), _pad_to_legal(seq_k)
    bq, bk = _block_sizes(sq, sk)
    counts = {"skipped": 0, "interior": 0, "diagonal": 0}
    for qi in range(sq // bq):
        for kj in range(sk // bk):
            live, masked = _pair_class(qi, kj, bq, bk, seq_k - seq_q, seq_k,
                                       causal)
            counts["diagonal" if masked else "interior" if live
                   else "skipped"] += 1
    return counts


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _init_state(kj, m_scr, l_scr, acc_scr):
    """The flash (m, l, acc) state starts afresh at a q block's first kv
    step."""
    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])


def _attend_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *, qi, kj,
                  live, masked, scale, causal, block_q, block_k, kv_len,
                  offset, keep=None):
    """The online-softmax recurrence over one (q block, kv block) pair,
    the body both forward entries share (training's ``flash_fwd`` and the
    serving ``flash_prefill``), taken only where ``live`` and masked only
    where ``masked`` (``_pair_class``).  ``kv_len`` is a static int
    (training) or a scalar read in the kernel (serving); ``keep(rows,
    cols)``, where given, is the dropout mask of P.V."""
    cut_len = _cuts_length(kv_len, block_k)

    def step(mask):
        # dots stay in the input dtype (bf16 on the fast path) with fp32
        # accumulation — casting to fp32 would run the MXU at 1/4 rate
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        if mask or keep is not None:
            rows, cols = _positions(qi, kj, block_q, block_k)
        if mask:
            s = _mask_scores(s, rows, cols, kv_len=kv_len, offset=offset,
                             causal=causal, cut_len=cut_len)
        # stats are lane-broadcast (bq, _LANES) tiles, all lanes equal
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _stat_tile(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        # PV accumulation uses the dropped probabilities; the softmax
        # normalizer l does not (dropout applies after normalization)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        if keep is not None:
            p = jnp.where(keep(rows, cols), p, 0.0)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    _by_class(live, masked, step, causal or cut_len)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                kv_len, offset, dropout_p):
    # 3D grid (bh, q block, kv block): k/v arrive as per-kv-block tiles and
    # the flash (m, l, acc) state lives in VMEM scratch across the innermost
    # kv steps — residency is O(block) in sequence length (a 2D grid that
    # kept full k/v resident hit the 16MB scoped-vmem limit at S=8192 f32).
    # Queries are bottom-right aligned against the REAL key length
    # (``offset`` = kv_len - q_len over unpadded lengths).
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    seed = seed_ref[0, 0].astype(jnp.uint32)
    _init_state(kj, m_scr, l_scr, acc_scr)
    keep = None
    if dropout_p > 0.0:
        keep = lambda rows, cols: _keep_mask(  # noqa: E731
            seed, bh, rows, cols, dropout_p)
    live, masked = _pair_class(qi, kj, block_q, block_k, offset, kv_len,
                               causal)
    _attend_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, qi=qi, kj=kj,
                  live=live, masked=masked, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, kv_len=kv_len,
                  offset=offset, keep=keep)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...]
                    / (l_safe[:, :1] * (1.0 - dropout_p))).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _flash_fwd(q, k, v, seed, scale, causal, dropout_p, kv_len, offset):
    from jax.experimental.pallas import tpu as pltpu
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, kv_len=kv_len, offset=offset,
        dropout_p=dropout_p)
    at_q = lambda b, i, j: (b, i, 0)  # noqa: E731
    at_kv = _kv_map(bq, bk, offset, kv_len, causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),       # seed
            pl.BlockSpec((1, bq, d), at_q),
            pl.BlockSpec((1, bk, d), at_kv),
            pl.BlockSpec((1, bk, d), at_kv),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), at_q),
            pl.BlockSpec((1, bq, _LANES), at_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # m
            pltpu.VMEM((bq, _LANES), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),        # acc
        ],
        name="flash_fwd",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(seed, q, k, v)
    return out, lse[:, :, 0]  # keep the compact (bh, sq) form as residual


# ---------------------------------------------------------------------------
# Backward (recompute): dkdv over KV blocks, dq over Q blocks
# ---------------------------------------------------------------------------
def _replay(q, k, v, do, lse, delta, seed, bh, *, qi, kj, mask, scale,
            causal, block_q, block_k, kv_len, offset, dropout_p, cut_len):
    """One pair's probabilities and score gradients replayed from the saved
    ``lse`` and ``delta``: ``(pd, ds)``, ``pd`` the dropped probabilities
    that weighed V, ``ds`` the gradient of the (scaled) scores."""
    s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
    if mask or dropout_p > 0.0:
        rows, cols = _positions(qi, kj, block_q, block_k)
    if mask:
        s = _mask_scores(s, rows, cols, kv_len=kv_len, offset=offset,
                         causal=causal, cut_len=cut_len)
    p = jnp.exp(s - lse)                                  # (bq, bk)
    if dropout_p > 0.0:
        pd = jnp.where(_keep_mask(seed, bh, rows, cols, dropout_p),
                       p * (1.0 / (1.0 - dropout_p)), 0.0)
    else:
        pd = p
    dp = _dot(do, v, (((1,), (1,)), ((), ())))
    return pd, (pd * dp - p * delta) * scale


def _dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                 kv_len, offset, dropout_p):
    # 3D grid (bh, kv block, q block): q/do/lse/delta arrive as per-q-block
    # tiles, so VMEM residency is O(block) — a 2D grid that kept the full
    # sequence resident hit the 16MB scoped-vmem limit at S=8192.  dk/dv
    # accumulate in the (revisited) f32 output blocks across the innermost
    # q-block steps.
    bh = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    seed = seed_ref[0, 0].astype(jnp.uint32)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    cut_len = _cuts_length(kv_len, block_k)

    def accumulate(mask):
        q = q_ref[0]                                      # (bq, d)
        do = do_ref[0]
        # lane-broadcast stats: every lane holds the row's value, so widening
        # to block_k lanes gives an elementwise-ready (bq, bk) tile
        pd, ds = _replay(
            q, k_ref[0], v_ref[0], do, _stat_tile(lse_ref[0], block_k),
            _stat_tile(delta_ref[0], block_k), seed, bh, qi=qi, kj=kj,
            mask=mask, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, kv_len=kv_len, offset=offset,
            dropout_p=dropout_p, cut_len=cut_len)
        dv_ref[0] += _dot(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())))
        dk_ref[0] += _dot(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())))

    live, masked = _pair_class(qi, kj, block_q, block_k, offset, kv_len,
                               causal)
    _by_class(live, masked, accumulate, causal or cut_len)


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, *, scale, causal, block_q, block_k, kv_len,
               offset, dropout_p):
    # 3D grid (bh, q block, kv block), mirroring _dkdv_kernel: k/v arrive
    # per-kv-block and dq accumulates in the revisited f32 output block.
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    seed = seed_ref[0, 0].astype(jnp.uint32)

    @pl.when(kj == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    cut_len = _cuts_length(kv_len, block_k)

    def accumulate(mask):
        k = k_ref[0]
        _, ds = _replay(
            q_ref[0], k, v_ref[0], do_ref[0],
            _stat_tile(lse_ref[0], block_k),  # lane-broadcast → (bq, bk)
            _stat_tile(delta_ref[0], block_k), seed, bh, qi=qi, kj=kj,
            mask=mask, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, kv_len=kv_len, offset=offset,
            dropout_p=dropout_p, cut_len=cut_len)
        dq_ref[0] += _dot(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())))

    live, masked = _pair_class(qi, kj, block_q, block_k, offset, kv_len,
                               causal)
    _by_class(live, masked, accumulate, causal or cut_len)


def _flash_bwd(scale, causal, dropout_p, kv_len, offset, res, g):
    from jax.experimental.pallas import tpu as pltpu
    q, k, v, seed, out, lse = res
    do = g
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # NOTE with dropout, out includes the 1/(1-p) rescale; delta =
    # rowsum(do * out) is exactly sum_k dP_ik P_ik of the dropped softmax
    # backward, so the standard recurrence still holds.
    lse_b = jnp.broadcast_to(lse[..., None], (bh, sq, _LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (bh, sq, _LANES))
    static = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  kv_len=kv_len, offset=offset, dropout_p=dropout_p)
    nq, nk = sq // bq, sk // bk

    def call(kernel, grid, at_q, at_kv, out_specs, out_shape, name):
        return pl.pallas_call(
            functools.partial(kernel, **static),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1), lambda b, x, y: (0, 0)),    # seed
                pl.BlockSpec((1, bq, d), at_q),                  # q
                pl.BlockSpec((1, bk, d), at_kv),                 # k
                pl.BlockSpec((1, bk, d), at_kv),                 # v
                pl.BlockSpec((1, bq, d), at_q),                  # do
                pl.BlockSpec((1, bq, _LANES), at_q),             # lse
                pl.BlockSpec((1, bq, _LANES), at_q),             # delta
            ],
            out_specs=out_specs, out_shape=out_shape, name=name,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_interpret(),
        )(seed, q, k, v, do, lse_b, delta_b)

    # f32 outputs: they double as the accumulators across the innermost
    # steps (Mosaic keeps a revisited output block in VMEM until the
    # revisit chain ends)
    kv_block = lambda b, j, i: (b, j, 0)  # noqa: E731
    dk, dv = call(
        _dkdv_kernel, (bh, nk, nq),
        _q_map(bq, bk, offset, kv_len, causal, nq), kv_block,
        [pl.BlockSpec((1, bk, d), kv_block),
         pl.BlockSpec((1, bk, d), kv_block)],
        [jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
         jax.ShapeDtypeStruct((bh, sk, d), jnp.float32)],
        "flash_bwd_dkdv")
    q_block = lambda b, i, j: (b, i, 0)  # noqa: E731
    dq = call(
        _dq_kernel, (bh, nq, nk), q_block,
        _kv_map(bq, bk, offset, kv_len, causal),
        pl.BlockSpec((1, bq, d), q_block),
        jax.ShapeDtypeStruct((bh, sq, d), jnp.float32), "flash_bwd_dq")
    seed_zero = np.zeros(seed.shape, jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            seed_zero)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_core(q, k, v, seed, scale, causal, dropout_p, kv_len,
                          offset):
    out, _ = _flash_fwd(q, k, v, seed, scale, causal, dropout_p, kv_len,
                        offset)
    return out


def _core_fwd(q, k, v, seed, scale, causal, dropout_p, kv_len, offset):
    out, lse = _flash_fwd(q, k, v, seed, scale, causal, dropout_p, kv_len,
                          offset)
    return out, (q, k, v, seed, out, lse)


_flash_attention_core.defvjp(_core_fwd, _flash_bwd)


def _pad_seq(x, target):
    pad = target - x.shape[2]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, dropout_p: float = 0.0,
                    training: bool = True, seed=None):
    """Fused attention over (batch, heads, seq, head_dim) inputs.

    Matches ``F.scaled_dot_product_attention(..., is_causal=causal)``
    numerics (bottom-right causal alignment) without materializing the
    (seq, seq) probabilities.  Ragged sequence lengths are auto-padded;
    ``dropout_p > 0`` stays on the fused path with an in-kernel
    counter-based mask (deterministic given ``seed``; when ``seed`` is
    None one is drawn from the framework RNG stream)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    enforce(k.shape == (b, h, sk, d) and v.shape == (b, h, sk, d),
            f"k/v shape mismatch: q={q.shape} k={k.shape} v={v.shape}")
    if scale is None:
        scale = d ** -0.5
    if not training:
        dropout_p = 0.0
    if dropout_p > 0.0:
        if seed is None:
            # op_key() honors key_scope, so the per-step traced key (not a
            # trace-time constant) varies the mask across jitted steps
            from ..framework import random as fw_random
            seed = jax.random.randint(fw_random.op_key(), (), 0,
                                      np.iinfo(np.int32).max, jnp.int32)
        seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    else:
        seed_arr = jnp.zeros((1, 1), jnp.int32)
    sq_pad, sk_pad = _pad_to_legal(sq), _pad_to_legal(sk)
    qf = _pad_seq(q, sq_pad).reshape(b * h, sq_pad, d)
    kf = _pad_seq(k, sk_pad).reshape(b * h, sk_pad, d)
    vf = _pad_seq(v, sk_pad).reshape(b * h, sk_pad, d)
    out = _flash_attention_core(qf, kf, vf, seed_arr, float(scale),
                                bool(causal), float(dropout_p), sk,
                                sk - sq)
    return out.reshape(b, h, sq_pad, d)[:, :, :sq, :]


# ---------------------------------------------------------------------------
# Serving prefill: causal attention over a padded chunk, bounded by each
# row's real length (a scalar-prefetch operand), forward only
# ---------------------------------------------------------------------------
def _prefill_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                    acc_scr, *, scale, block_q, block_k, heads):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    length = lens_ref[pl.program_id(0) // heads]
    _init_state(kj, m_scr, l_scr, acc_scr)
    # a pair on or under the diagonal whose q and kv blocks both start
    # inside the real length; a q block wholly past it keeps the zeros
    live, masked = _pair_class(qi, kj, block_q, block_k, 0, length, True)
    _attend_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, qi=qi, kj=kj,
                  live=live & (qi * block_q < length), masked=masked,
                  scale=scale, causal=True, block_q=block_q,
                  block_k=block_k, kv_len=length, offset=0)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "block_k", "heads"))
def _prefill_call(q, k, v, lengths, scale, block_q, block_k, heads):
    """``pallas_call`` of the prefill kernel over (batch*heads, seq, d).
    Jitted, so a step program lowers the kernel once for all its layers
    (Mosaic lowering is ~0.1 s a call site on this sandbox's CPU: 24 a
    GPT-XL prefill program, paid on every start, cached or not)."""
    from jax.experimental.pallas import tpu as pltpu
    bh, s, d = q.shape

    def q_map(b, i, j, lens):
        # a q block past the length computes nothing: keep the last live
        # one's tile instead of fetching its own
        last = jnp.maximum(lens[b // heads] - 1, 0) // block_q
        return b, jnp.minimum(i, last), 0

    def kv_map(b, i, j, lens):
        # a skipped pair repeats the previous step's block index, so its
        # DMA is not issued: clamp to the causal diagonal and the length,
        # and a dead q block to the block the last live one ended on
        n = lens[b // heads]
        return b, jnp.where(
            i * block_q < n,
            jnp.minimum(j, _last_kv(i, block_q, block_k, 0, n, True)),
            _last_kv(i, block_q, block_k, 0, n, False)), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(bh, s // block_q, s // block_k),
        in_specs=[pl.BlockSpec((1, block_q, d), q_map),
                  pl.BlockSpec((1, block_k, d), kv_map),
                  pl.BlockSpec((1, block_k, d), kv_map)],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j, lens:
                               (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),        # acc
        ])
    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, heads=heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        name="flash_prefill",
        interpret=_interpret(),
    )(lengths, q, k, v)


def flash_prefill(q, k, v, lengths, scale: Optional[float] = None):
    """Causal attention of a serving prefill chunk: ``q, k, v`` (batch,
    heads, seq, head_dim) with the chunk as the whole context, row ``b``
    real up to ``lengths[b]`` (int32, traced) and padding past it.

    The kernel visits only the block pairs on or under the diagonal whose
    q and kv blocks start inside the row's length, and skips the DMA of
    every other; rows past the length come out zero.  Real rows equal
    :func:`flash_attention`'s causal result (one block body: bf16 dots,
    float32 scores and statistics).  Forward only: no residuals, no
    ``lse``."""
    b, h, s, d = q.shape
    enforce(k.shape == q.shape and v.shape == q.shape,
            f"q/k/v shape mismatch: q={q.shape} k={k.shape} v={v.shape}")
    if scale is None:
        scale = d ** -0.5
    s_pad = _pad_to_legal(s)
    bq, bk = _block_sizes(s_pad, s_pad)
    flat = lambda x: _pad_seq(x, s_pad).reshape(b * h, s_pad, d)  # noqa
    out = _prefill_call(flat(q), flat(k), flat(v.astype(q.dtype)),
                        jnp.asarray(lengths, jnp.int32).reshape(b),
                        scale=float(scale), block_q=bq, block_k=bk, heads=h)
    return out.reshape(b, h, s_pad, d)[:, :, :s, :]


# ---------------------------------------------------------------------------
# Decode: single-step attention against a KV cache (reference CacheKV,
# fused_attention_op.cc:235) — memory-bound; the kernel streams only the
# cache blocks that hold real entries (dynamic trip count on cache_seqlen).
# ---------------------------------------------------------------------------
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale, block_k):
    q = q_ref[0]                                          # (sq, d)
    kv_len = len_ref[0, 0]
    num_iter = (kv_len + block_k - 1) // block_k          # dynamic

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        cols = j * block_k + lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_k), 1)
        s = jnp.where(cols < kv_len, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + _dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((q.shape[0],), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m, l, acc = lax.fori_loop(0, num_iter, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_kvcache(q, k_cache, v_cache, cache_seqlen,
                            scale: Optional[float] = None):
    """Decode-step attention: ``q`` (batch, heads, sq, head_dim) attends to
    ``k_cache/v_cache[:, :, :cache_seqlen]``.  ``cache_seqlen`` may be a
    traced scalar — the kernel's trip count is dynamic, so one compiled
    program serves every decode position (no per-step retrace)."""
    b, h, sq, d = q.shape
    smax = k_cache.shape[2]
    enforce(smax % 8 == 0,
            f"kv cache capacity {smax} must be a multiple of 8 "
            "(allocate the cache padded)")
    if scale is None:
        scale = d ** -0.5
    bk = min(_block_sizes(smax, smax)[1], smax)
    qf = q.reshape(b * h, sq, d)
    kf = k_cache.reshape(b * h, smax, d)
    vf = v_cache.reshape(b * h, smax, d)
    len_arr = jnp.asarray(cache_seqlen, jnp.int32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), block_k=bk),
        grid=(b * h,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, sq, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, smax, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, smax, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, sq, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        name="flash_decode",
        interpret=_interpret(),
    )(len_arr, qf, kf, vf)
    return out.reshape(b, h, sq, d)
