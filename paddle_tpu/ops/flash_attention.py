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
(batch*heads, seq, head_dim) for the kernel; grid = (batch*heads, q block,
kv block) with kv innermost — the flash (m, l, acc) recurrence lives in
VMEM scratch across the kv steps, in fp32, so per-step residency is
O(block) and sequence length is HBM-bound (S=65536 runs single-chip).
Backward is recompute-based (no probability tensor saved): a dkdv kernel on a
(bh, kv block, q block) grid accumulating into revisited f32 output blocks,
and a dq kernel over Q blocks, both replaying p = exp(qk - lse).  Backward
VMEM residency is O(block), so sequence length is bounded by HBM, not the
16MB scoped-vmem limit (S=8192 fwd+bwd measured 30ms vs 737ms for XLA
attention on v5e in July; a claim to re-measure).

Causal masking is block-skipped: programs never visit KV blocks strictly
above the diagonal, so the causal fwd does ~half the FLOPs — the fusion
`fused_softmax_mask_upper_triangle` only saves bandwidth, not compute.

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
    # swept on v5e (3D-grid kernels, bh·S·d with d=64, best-of-3 fwd+bwd;
    # July figures, to re-measure): at S=2048, 512/512 = 13.9ms vs
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
# Forward
# ---------------------------------------------------------------------------
def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                kv_len, offset, dropout_p):
    # 3D grid (bh, q block, kv block): k/v arrive as per-kv-block tiles and
    # the flash (m, l, acc) state lives in VMEM scratch across the innermost
    # kv steps — residency is O(block) in sequence length (a 2D grid that
    # kept full k/v resident hit the 16MB scoped-vmem limit at S=8192 f32).
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_kv = pl.num_programs(2)
    seed = seed_ref[0, 0].astype(jnp.uint32)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # visit only blocks that touch real keys and (causal) the lower
    # triangle; queries are bottom-right aligned against the REAL key
    # length (``offset`` = kv_len - q_len over unpadded lengths)
    work = kj * block_k < kv_len
    if causal:
        work &= (qi + 1) * block_q - 1 + offset >= kj * block_k

    @pl.when(work)
    def _step():
        # dots stay in the input dtype (bf16 on the fast path) with fp32
        # accumulation — casting to fp32 would run the MXU at 1/4 rate
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        rows = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < kv_len
        if causal:
            valid = valid & (rows + offset >= cols)
        s = jnp.where(valid, s, _NEG_INF)
        # stats are lane-broadcast (bq, _LANES) tiles, all lanes equal
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _stat_tile(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        # PV accumulation uses the dropped probabilities; the softmax
        # normalizer l does not (dropout applies after normalization)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        if dropout_p > 0.0:
            p = jnp.where(_keep_mask(seed, bh, rows, cols, dropout_p),
                          p, 0.0)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(kj == num_kv - 1)
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
    grid = (bh, sq // bq, sk // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, kv_len=kv_len, offset=offset,
        dropout_p=dropout_p)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),       # seed
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
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
        interpret=_interpret(),
    )(seed, q, k, v)
    return out, lse[:, :, 0]  # keep the compact (bh, sq) form as residual


# ---------------------------------------------------------------------------
# Backward (recompute): dkdv over KV blocks, dq over Q blocks
# ---------------------------------------------------------------------------
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
    keep_scale = 1.0 / (1.0 - dropout_p)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    # causal block-skip: a block whose every (row, col) pair sits strictly
    # above the diagonal contributes nothing; padded-KV blocks likewise
    work = kj * block_k < kv_len
    if causal:
        work &= (qi + 1) * block_q - 1 + offset >= kj * block_k

    @pl.when(work)
    def _accumulate():
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]
        q = q_ref[0]                                      # (bq, d)
        do = do_ref[0]
        # lane-broadcast stats: every lane holds the row's value, so widening
        # to block_k lanes gives an elementwise-ready (bq, bk) tile
        lse = _stat_tile(lse_ref[0], block_k)
        delta = _stat_tile(delta_ref[0], block_k)
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        rows = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < kv_len
        if causal:
            valid = valid & (rows + offset >= cols)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        if dropout_p > 0.0:
            pd = jnp.where(_keep_mask(seed, bh, rows, cols, dropout_p),
                           p * keep_scale, 0.0)
        else:
            pd = p
        dv_ref[0] += _dot(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())))
        dp = _dot(do, v, (((1,), (1,)), ((), ())))
        ds = (pd * dp - p * delta) * scale
        dk_ref[0] += _dot(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())))


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, *, scale, causal, block_q, block_k, kv_len,
               offset, dropout_p):
    # 3D grid (bh, q block, kv block), mirroring _dkdv_kernel: k/v arrive
    # per-kv-block and dq accumulates in the revisited f32 output block.
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    seed = seed_ref[0, 0].astype(jnp.uint32)
    keep_scale = 1.0 / (1.0 - dropout_p)

    @pl.when(kj == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    work = kj * block_k < kv_len
    if causal:
        work &= (qi + 1) * block_q - 1 + offset >= kj * block_k

    @pl.when(work)
    def _accumulate():
        q = q_ref[0]
        do = do_ref[0]
        lse = _stat_tile(lse_ref[0], block_k)  # lane-broadcast → (bq, bk)
        delta = _stat_tile(delta_ref[0], block_k)
        k = k_ref[0]
        v = v_ref[0]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        rows = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < kv_len
        if causal:
            valid = valid & (rows + offset >= cols)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)
        if dropout_p > 0.0:
            pd = jnp.where(_keep_mask(seed, bh, rows, cols, dropout_p),
                           p * keep_scale, 0.0)
        else:
            pd = p
        dp = _dot(do, v, (((1,), (1,)), ((), ())))
        ds = (pd * dp - p * delta) * scale
        dq_ref[0] += _dot(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())))


def _flash_bwd(scale, causal, dropout_p, kv_len, offset, res, g):
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

    dkdv = functools.partial(
        _dkdv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        kv_len=kv_len, offset=offset, dropout_p=dropout_p)
    # f32 outputs: they double as the cross-q-block accumulators (Mosaic
    # keeps a revisited output block in VMEM until the revisit chain ends)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(bh, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, j, i: (0, 0)),        # seed
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, _LANES), lambda b, j, i: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, _LANES),
                         lambda b, j, i: (b, i, 0)),              # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        name="flash_bwd_dkdv",
        interpret=_interpret(),
    )(seed, q, k, v, do, lse_b, delta_b)
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)

    dqk = functools.partial(
        _dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        kv_len=kv_len, offset=offset, dropout_p=dropout_p)
    dq = pl.pallas_call(
        dqk,
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),         # seed
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, _LANES),
                         lambda b, i, j: (b, i, 0)),              # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(seed, q, k, v, do, lse_b, delta_b).astype(q.dtype)
    seed_zero = np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, seed_zero


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
