"""Block-level fused transformer execution (ISSUE 7).

Reference semantics: the fused_multi_transformer family — one CUDA op per
decoder block covering pre-LN → QKV GEMM → (rope) → FMHA → out-proj →
bias+dropout+residual, plus fused_feedforward for the MLP half
(operators/fused/fused_multi_transformer_op.cu, fused_attention_op.cc,
fused_feedforward_op.cc).  PAPERS.md backs the block-level ambition:
*ClusterFusion++* fuses whole-block decoding, *Neptune* shows
operator-fusion locality wins beyond what a compiler pass finds.

TPU-native layout of that idea.  The block is expressed as THREE Pallas
kernel surfaces chained under one op call per block half, each owning the
piece XLA cannot (or measurably does not) fuse on its own:

  attention half (``fused_attention_block``):
    [K1 ln_linear]   LN(x) @ W_qkv + b   — one read of x; the normalized
                     activations never round-trip HBM (VMEM scratch),
                     unlike the LN-then-GEMM pair XLA emits.
    [rope]           two multiplies against the lru-cached cos/sin tables
                     (ops/fused.py) — optional, GPT-NeoX formulation.
    [flash fwd/bwd]  the existing ops/flash_attention.py kernels, with
                     their in-kernel counter-hash attention dropout.
    [K2 epilogue]    attn @ W_out + b → dropout → +residual — the GEMM
                     epilogue and the residual add in one output pass.
  FFN half (``fused_ffn_block``):
    [K3 ffn]         LN → GEMM → act(+drop) → GEMM → drop → +residual as
                     ONE kernel: the (rows, ffn) intermediate lives only
                     as a VMEM tile per grid step, never in HBM.

Why the boundary sits here and not at "one kernel for the whole block":
the out-projection contracts over *all heads* while the flash grid is
one-head-per-program, so folding the epilogue into the attention kernel
would need cross-program reduction; chaining kernels keeps each at
O(block) VMEM residency (same argument as the flash bwd split).
docs/ARCHITECTURE.md "Fused block execution" has the full diagram.

Differentiation: every Pallas surface carries a ``jax.custom_vjp`` whose
backward is *recompute-based* — it replays the cheap jnp composition (two
extra GEMMs; XLA fuses those epilogues fine in backward) and, for the
attention segment, re-enters ``_flash_attention_core`` so the flash
dkdv/dq Pallas kernels do the heavy lifting.  Nothing beyond the residual
stream and the per-row lse is saved.

Dropout everywhere in the block is the counter-based hash of
ops/flash_attention.py (the reference's Philox-offset trick): the keep
mask for (salt, row, col) is a pure function of a traced int32 seed, so
forward, recompute-backward, and the interpret-mode oracle regenerate
bit-identical masks with zero HBM mask traffic — and the jnp reference
route is deterministic given the same seed (the cross-route parity and
dropout-determinism tests in tests/test_fused_block.py rely on this).

Routing (same pattern as inference/paged_attention.py): the Pallas route
on a real TPU, the pure-jnp reference route elsewhere — the reference IS
the tier-1/CPU default and the numerics oracle.  ``PTPU_FUSED_BLOCK=
pallas|reference`` forces a route; ``FLAGS_pallas_interpret_routing``
also forces the kernels (interpret mode) for cross-path tests.  Shapes a
Mosaic block can't tile (rows % 8, GEMM cols % 128) take the reference
route; which route a compiled program got is in its HLO (the
``fused_ln_linear`` / ``fused_linear_residual`` / ``fused_ffn``
``tpu_custom_call`` s — ``tests/test_tpu_hw.py`` asserts them).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..amp import state as amp_state
from ..framework import random as fw_random
from ..framework.errors import enforce
from .flash_attention import (_NEG_INF, _dot, _interpret, _keep_mask,
                              flash_attention, flash_attention_kvcache)

__all__ = ["fused_ln_linear", "fused_linear_residual",
           "fused_attention_block", "fused_ffn_block",
           "fused_attention_block_kvcache", "fused_block_route"]

FUSED_BLOCK_ENV = "PTPU_FUSED_BLOCK"

# distinct dropout sub-streams per epilogue (the bh slot of the flash hash;
# attention itself salts with the real bh index)
_SALT_RESID = 0x52455344
_SALT_FFN1 = 0x46464E31
_SALT_FFN2 = 0x46464E32


def _arr(x):
    return x.__jax_array__() if hasattr(x, "__jax_array__") else x


def fused_block_route() -> str:
    """'pallas' or 'reference' — which implementation the fused-block ops
    take on this backend (before per-shape legality)."""
    # deliberate trace-time pin: the route IS part of the trace signature
    # (a retrace re-reads it; flipping mid-run is not supported)
    forced = os.environ.get(FUSED_BLOCK_ENV, "")  # noqa: trace — route pinned at trace time by design
    if forced in ("pallas", "reference"):
        return forced
    from ..framework import flags as _flags
    try:
        if not _flags.get_flag("use_pallas_kernels"):
            return "reference"
        if _flags.get_flag("pallas_interpret_routing"):
            return "pallas"
    except KeyError:
        pass  # flags not registered (minimal import) — fall to backend
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _pallas_ok(rows: int, *gemm_cols: int) -> bool:
    """Mosaic tiling legality for the block kernels: row blocks are
    sublane-aligned, every GEMM output/ffn column count tiles by 128."""
    return rows % 8 == 0 and all(c % 128 == 0 for c in gemm_cols)


# Block sizes are capped so that one grid step's double-buffered tiles stay
# inside Mosaic's 16 MiB scoped-VMEM limit at hidden 2048 / ffn 8192: 4-byte
# operands take half the rows and half the columns of 2-byte ones (at
# 256 x 512 the f32 kernels asked for 22-23 MiB and failed to compile).
def _pick_rows(n: int, dtype, cap: int = 256) -> int:
    cap = cap if jnp.dtype(dtype).itemsize <= 2 else cap // 2
    for b in (256, 128, 64, 32, 16, 8):
        if b <= cap and n % b == 0:
            return b
    return n


def _pick_cols(n: int, dtype, cap: int = 512) -> int:
    cap = cap if jnp.dtype(dtype).itemsize <= 2 else cap // 2
    for b in (512, 256, 128):
        if b <= cap and n % b == 0:
            return b
    return n


def _seed_or_draw(seed, need: bool):
    """A traced int32 scalar seed for the hash-dropout streams; drawn from
    the framework RNG (key_scope-aware, so jitted steps vary it) when the
    caller didn't pass one."""
    if not need:
        return jnp.zeros((), jnp.int32)
    if seed is None:
        seed = jax.random.randint(fw_random.op_key(), (), 0,
                                  np.iinfo(np.int32).max, jnp.int32)
    return jnp.asarray(seed, jnp.int32)


def _hash_drop(y, seed, salt: int, p: float, rows=None, cols=None):
    """jnp rendering of the kernels' in-register dropout: keep(salt, row,
    col) from the flash counter hash, post-normalization 1/(1-p) rescale.
    ``y`` is (n, c); row/col default to global indices over y."""
    n, c = y.shape
    if rows is None:
        rows = lax.broadcasted_iota(jnp.int32, (n, c), 0)
    if cols is None:
        cols = lax.broadcasted_iota(jnp.int32, (n, c), 1)
    keep = _keep_mask(seed.astype(jnp.uint32), jnp.uint32(salt),
                      rows, cols, p)
    return jnp.where(keep, y / (1.0 - p), jnp.zeros((), y.dtype))


def _ln_f32(x, g, beta, epsilon: float):
    """LayerNorm in f32 (the oracle F.layer_norm math, amp-independent),
    returned in f32 — callers cast to the GEMM dtype."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + epsilon)
    if g is not None:
        y = y * g.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y


# ---------------------------------------------------------------------------
# K1: fused pre-LN + GEMM (the LN → QKV projection pair as one HBM pass)
# ---------------------------------------------------------------------------
def _ln_linear_kernel(x_ref, w_ref, b_ref, g_ref, beta_ref, o_ref, lnx_scr,
                      *, epsilon):
    # grid (row block, col block), cols innermost: the normalized row block
    # is computed once at j == 0 and served from VMEM scratch for every
    # column tile — x is read once, LN(x) never lands in HBM
    @pl.when(pl.program_id(1) == 0)
    def _ln():
        xf = x_ref[...].astype(jnp.float32)
        mean = jnp.mean(xf, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + epsilon)
        y = (y * g_ref[...].astype(jnp.float32)
             + beta_ref[...].astype(jnp.float32))
        lnx_scr[...] = y.astype(lnx_scr.dtype)

    o_ref[...] = (_dot(lnx_scr[...], w_ref[...], (((1,), (0,)), ((), ())))
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_linear_pallas(x, w, b, g, beta, epsilon):
    from jax.experimental.pallas import tpu as pltpu
    n, h = x.shape
    cols = w.shape[1]
    br, bc = _pick_rows(n, w.dtype), _pick_cols(cols, w.dtype)
    return pl.pallas_call(
        functools.partial(_ln_linear_kernel, epsilon=epsilon),
        grid=(n // br, cols // bc),
        in_specs=[
            pl.BlockSpec((br, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, h), lambda i, j: (0, 0)),
            pl.BlockSpec((1, h), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, cols), w.dtype),
        scratch_shapes=[pltpu.VMEM((br, h), w.dtype)],
        name="fused_ln_linear",
        interpret=_interpret(),
    )(x, w, b.reshape(1, -1), g.reshape(1, -1), beta.reshape(1, -1))


def _ln_linear_ref(x, w, b, g, beta, epsilon):
    y = _ln_f32(x, g, beta, epsilon).astype(w.dtype)
    return jnp.matmul(y, w) + b.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ln_linear_p(x, w, b, g, beta, epsilon):
    return _ln_linear_pallas(x, w, b, g, beta, epsilon)


def _ln_linear_p_fwd(x, w, b, g, beta, epsilon):
    return _ln_linear_p(x, w, b, g, beta, epsilon), (x, w, b, g, beta)


def _ln_linear_p_bwd(epsilon, res, gout):
    x, w, b, g, beta = res
    # recompute-based: two GEMMs + the LN chain rule, all XLA-fused
    _, vjp = jax.vjp(
        lambda x_, w_, b_, g_, bb_: _ln_linear_ref(x_, w_, b_, g_, bb_,
                                                   epsilon),
        x, w, b, g, beta)
    return vjp(gout)


_ln_linear_p.defvjp(_ln_linear_p_fwd, _ln_linear_p_bwd)


def fused_ln_linear(x, w, b, ln_scale, ln_bias, *, epsilon: float = 1e-5):
    """``LN(x) @ w + b`` over the last dim of ``x`` — the pre-LN + QKV
    (or pre-LN + fc_in) pair as one kernel pass.  LN runs in f32 on the
    raw activations; the GEMM runs in the AMP dtype (one Pallas kernel on
    TPU, the jnp composition elsewhere)."""
    x, w = _arr(x), _arr(w)
    b, g, beta = _arr(b), _arr(ln_scale), _arr(ln_bias)
    _, w = amp_state.cast_for_op("linear", x, w)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if (fused_block_route() == "pallas"
            and _pallas_ok(x2.shape[0], w.shape[1])):
        out = _ln_linear_p(x2, w, b, g, beta, float(epsilon))
    else:
        out = _ln_linear_ref(x2, w, b, g, beta, float(epsilon))
    return out.reshape(shape[:-1] + (w.shape[1],))


# ---------------------------------------------------------------------------
# K2: GEMM epilogue — y @ W + b → dropout → + residual in one output pass
# ---------------------------------------------------------------------------
def _linear_residual_kernel(seed_ref, x_ref, w_ref, b_ref, r_ref, o_ref, *,
                            dropout_p, salt, block_r, block_c):
    y = (_dot(x_ref[...], w_ref[...], (((1,), (0,)), ((), ())))
         + b_ref[...].astype(jnp.float32))
    if dropout_p > 0.0:
        i, j = pl.program_id(0), pl.program_id(1)
        rows = i * block_r + lax.broadcasted_iota(
            jnp.int32, (block_r, block_c), 0)
        cols = j * block_c + lax.broadcasted_iota(
            jnp.int32, (block_r, block_c), 1)
        keep = _keep_mask(seed_ref[0, 0].astype(jnp.uint32),
                          jnp.uint32(salt), rows, cols, dropout_p)
        y = jnp.where(keep, y / (1.0 - dropout_p), 0.0)
    o_ref[...] = (r_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


def _linear_residual_pallas(x, w, b, r, seed, dropout_p, salt):
    n, k = x.shape
    cols = w.shape[1]
    br, bc = _pick_rows(n, w.dtype), _pick_cols(cols, w.dtype)
    return pl.pallas_call(
        functools.partial(_linear_residual_kernel, dropout_p=dropout_p,
                          salt=salt, block_r=br, block_c=bc),
        grid=(n // br, cols // bc),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),     # seed
            pl.BlockSpec((br, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),   # residual
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, cols), r.dtype),
        name="fused_linear_residual",
        interpret=_interpret(),
    )(seed.reshape(1, 1), x, w, b.reshape(1, -1), r)


def _linear_residual_ref(x, w, b, r, seed, dropout_p, salt):
    y = (jnp.matmul(x, w).astype(jnp.float32) + b.astype(jnp.float32))
    if dropout_p > 0.0:
        y = _hash_drop(y, seed, salt, dropout_p)
    return (r.astype(jnp.float32) + y).astype(r.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _linear_residual_p(x, w, b, r, seed, dropout_p, salt):
    return _linear_residual_pallas(x, w, b, r, seed, dropout_p, salt)


def _linear_residual_p_fwd(x, w, b, r, seed, dropout_p, salt):
    out = _linear_residual_p(x, w, b, r, seed, dropout_p, salt)
    return out, (x, w, b, r, seed)


def _linear_residual_p_bwd(dropout_p, salt, res, gout):
    x, w, b, r, seed = res
    _, vjp = jax.vjp(
        lambda x_, w_, b_, r_: _linear_residual_ref(x_, w_, b_, r_, seed,
                                                    dropout_p, salt),
        x, w, b, r)
    dx, dw, db, dr = vjp(gout)
    return dx, dw, db, dr, np.zeros(seed.shape, jax.dtypes.float0)


_linear_residual_p.defvjp(_linear_residual_p_fwd, _linear_residual_p_bwd)


def fused_linear_residual(x, w, b, residual, *, dropout_p: float = 0.0,
                          training: bool = True, seed=None,
                          salt: int = _SALT_RESID):
    """``residual + dropout(x @ w + b)`` — the out-projection epilogue of
    the reference's fused_attention_op (bias+dropout+residual) with the
    hash-dropout mask regenerated in backward instead of stored."""
    x, w = _arr(x), _arr(w)
    b, residual = _arr(b), _arr(residual)
    x, w = amp_state.cast_for_op("linear", x, w)
    if not training:
        dropout_p = 0.0
    seed = _seed_or_draw(seed, dropout_p > 0.0)
    shape = residual.shape
    x2 = x.reshape(-1, x.shape[-1])
    r2 = residual.reshape(-1, shape[-1])
    if (fused_block_route() == "pallas"
            and _pallas_ok(x2.shape[0], w.shape[1])):
        out = _linear_residual_p(x2, w, b, r2, seed, float(dropout_p),
                                 int(salt))
    else:
        out = _linear_residual_ref(x2, w, b, r2, seed, float(dropout_p),
                                   int(salt))
    return out.reshape(shape)


# Mosaic lowers neither erf nor erfc (jax 0.9.0), so the kernel's exact GELU
# evaluates erf by the f32 rational approximation XLA itself uses on other
# backends (odd P(x^2)/Q(x^2) on [-4, 4]; max abs error 3.5e-7 against
# math.erf, measured over [-6, 6]).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _gelu_erf(x):
    z = jnp.clip(x * np.float32(0.7071067811865476), -4.0, 4.0)
    z2 = z * z
    p = jnp.full_like(z, _ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * z2 + np.float32(c)
    q = jnp.full_like(z, _ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * z2 + np.float32(c)
    return 0.5 * x * (1.0 + z * p / q)


# ---------------------------------------------------------------------------
# K3: the FFN half as ONE kernel — LN → GEMM → act(+drop) → GEMM → drop →
# + residual; the (rows, ffn) intermediate exists only as a VMEM tile
# ---------------------------------------------------------------------------
def _ffn_kernel(seed_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref, g_ref,
                beta_ref, o_ref, lnx_scr, acc_scr, *, epsilon, activation,
                dropout1, dropout2, block_r, block_f):
    i, j = pl.program_id(0), pl.program_id(1)
    nf = pl.num_programs(1)
    seed = seed_ref[0, 0].astype(jnp.uint32)

    @pl.when(j == 0)
    def _init():
        xf = x_ref[...].astype(jnp.float32)
        mean = jnp.mean(xf, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + epsilon)
        y = (y * g_ref[...].astype(jnp.float32)
             + beta_ref[...].astype(jnp.float32))
        lnx_scr[...] = y.astype(lnx_scr.dtype)
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    h = (_dot(lnx_scr[...], w1_ref[...], (((1,), (0,)), ((), ())))
         + b1_ref[...].astype(jnp.float32))
    h = _gelu_erf(h) if activation == "gelu" else jnp.maximum(h, 0.0)
    if dropout1 > 0.0:
        rows = i * block_r + lax.broadcasted_iota(
            jnp.int32, (block_r, block_f), 0)
        cols = j * block_f + lax.broadcasted_iota(
            jnp.int32, (block_r, block_f), 1)
        keep = _keep_mask(seed, jnp.uint32(_SALT_FFN1), rows, cols, dropout1)
        h = jnp.where(keep, h / (1.0 - dropout1), 0.0)
    acc_scr[...] += _dot(h.astype(w2_ref.dtype), w2_ref[...],
                         (((1,), (0,)), ((), ())))

    @pl.when(j == nf - 1)
    def _finalize():
        y = acc_scr[...] + b2_ref[...].astype(jnp.float32)
        if dropout2 > 0.0:
            hcols = y.shape[1]
            rows = i * block_r + lax.broadcasted_iota(
                jnp.int32, (block_r, hcols), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block_r, hcols), 1)
            keep = _keep_mask(seed, jnp.uint32(_SALT_FFN2), rows, cols,
                              dropout2)
            y = jnp.where(keep, y / (1.0 - dropout2), 0.0)
        o_ref[...] = (x_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


def _ffn_pallas(x, w1, b1, w2, b2, g, beta, seed, activation, dropout1,
                dropout2, epsilon):
    from jax.experimental.pallas import tpu as pltpu
    n, h = x.shape
    ffn = w1.shape[1]
    # x + lnx + acc + both weight tiles ≤ VMEM
    br = _pick_rows(n, w1.dtype, cap=128)
    bf = _pick_cols(ffn, w1.dtype)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, epsilon=epsilon,
                          activation=activation, dropout1=dropout1,
                          dropout2=dropout2, block_r=br, block_f=bf),
        grid=(n // br, ffn // bf),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),     # seed
            pl.BlockSpec((br, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, h), lambda i, j: (j, 0)),
            pl.BlockSpec((1, h), lambda i, j: (0, 0)),
            pl.BlockSpec((1, h), lambda i, j: (0, 0)),     # g
            pl.BlockSpec((1, h), lambda i, j: (0, 0)),     # beta
        ],
        # revisited across j; written once at the last ffn tile
        out_specs=pl.BlockSpec((br, h), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((br, h), w1.dtype),                 # LN(x)
            pltpu.VMEM((br, h), jnp.float32),              # W2 accumulator
        ],
        name="fused_ffn",
        interpret=_interpret(),
    )(seed.reshape(1, 1), x, w1, b1.reshape(1, -1), w2, b2.reshape(1, -1),
      g.reshape(1, -1), beta.reshape(1, -1))


def _ffn_ref(x, w1, b1, w2, b2, g, beta, seed, activation, dropout1,
             dropout2, epsilon):
    lnx = _ln_f32(x, g, beta, epsilon).astype(w1.dtype)
    h = (jnp.matmul(lnx, w1).astype(jnp.float32)
         + b1.astype(jnp.float32))
    h = jax.nn.gelu(h, approximate=False) if activation == "gelu" \
        else jnp.maximum(h, 0.0)
    if dropout1 > 0.0:
        h = _hash_drop(h, seed, _SALT_FFN1, dropout1)
    y = (jnp.matmul(h.astype(w2.dtype), w2).astype(jnp.float32)
         + b2.astype(jnp.float32))
    if dropout2 > 0.0:
        y = _hash_drop(y, seed, _SALT_FFN2, dropout2)
    return (x.astype(jnp.float32) + y).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _ffn_p(x, w1, b1, w2, b2, g, beta, seed, activation, dropout1,
           dropout2, epsilon):
    return _ffn_pallas(x, w1, b1, w2, b2, g, beta, seed, activation,
                       dropout1, dropout2, epsilon)


def _ffn_p_fwd(x, w1, b1, w2, b2, g, beta, seed, activation, dropout1,
               dropout2, epsilon):
    out = _ffn_p(x, w1, b1, w2, b2, g, beta, seed, activation, dropout1,
                 dropout2, epsilon)
    return out, (x, w1, b1, w2, b2, g, beta, seed)


def _ffn_p_bwd(activation, dropout1, dropout2, epsilon, res, gout):
    x, w1, b1, w2, b2, g, beta, seed = res
    _, vjp = jax.vjp(
        lambda *a: _ffn_ref(*a, seed, activation, dropout1, dropout2,
                            epsilon),
        x, w1, b1, w2, b2, g, beta)
    return vjp(gout) + (np.zeros(seed.shape, jax.dtypes.float0),)


_ffn_p.defvjp(_ffn_p_fwd, _ffn_p_bwd)


def fused_ffn_block(x, w1, b1, w2, b2, ln_scale, ln_bias, *,
                    activation: str = "gelu", dropout1: float = 0.0,
                    dropout2: float = 0.0, epsilon: float = 1e-5,
                    training: bool = True, seed=None):
    """The FFN half of a pre-LN decoder block as one fused op:

        out = x + drop2(W2 · act(drop1(W1 · LN(x) + b1)) + b2)

    One Pallas kernel on TPU (the (rows, ffn) intermediate never touches
    HBM); the jnp composition elsewhere.  ``activation`` ∈ {gelu, relu}."""
    enforce(activation in ("gelu", "relu"),
            f"fused_ffn_block: unsupported activation {activation!r}")
    x = _arr(x)
    w1, b1, w2, b2 = map(_arr, (w1, b1, w2, b2))
    g, beta = _arr(ln_scale), _arr(ln_bias)
    _, w1 = amp_state.cast_for_op("linear", x, w1)
    _, w2 = amp_state.cast_for_op("linear", x, w2)
    if not training:
        dropout1 = dropout2 = 0.0
    seed = _seed_or_draw(seed, dropout1 > 0.0 or dropout2 > 0.0)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if (fused_block_route() == "pallas"
            and _pallas_ok(x2.shape[0], w1.shape[1], w2.shape[1])):
        out = _ffn_p(x2, w1, b1, w2, b2, g, beta, seed, activation,
                     float(dropout1), float(dropout2), float(epsilon))
    else:
        out = _ffn_ref(x2, w1, b1, w2, b2, g, beta, seed, activation,
                       float(dropout1), float(dropout2), float(epsilon))
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# The attention half: K1 → rope → flash → K2 under one op call
# ---------------------------------------------------------------------------
def _split_heads(qkv, b, s, num_heads, head_dim):
    """(N, 3h) → q, k, v as (b, s, heads, d) — head-major column order,
    mirroring GPTAttention's fused-dim factorization."""
    qkv = qkv.reshape(b, s, num_heads, 3, head_dim)
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def _apply_rope(q, k, base: float):
    """GPT-NeoX rope on (b, s, heads, d) from the lru-cached tables —
    two multiplies per tensor at trace time (ops/fused.py satellite)."""
    from .fused import _rope_tables
    s, d = q.shape[1], q.shape[-1]
    cos, sin = _rope_tables(s, d, float(base))
    cs = cos[None, :, None, :]
    sn = sin[None, :, None, :]

    def rot(x):
        d2 = d // 2
        x1 = x[..., :d2].astype(jnp.float32)
        x2 = x[..., d2:].astype(jnp.float32)
        return jnp.concatenate(
            [x1 * cs - x2 * sn, x2 * cs + x1 * sn], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _attention_ref(q, k, v, scale, causal, dropout_p, seed):
    """jnp attention in (b, s, heads, d) layout — no transposes, hash
    attention-dropout with the flash kernels' exact (bh, row, col)
    indexing so both routes agree given one seed."""
    b, s, nh, _ = q.shape
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale).astype(
        jnp.float32)
    if causal:
        rows = lax.broadcasted_iota(jnp.int32, (s, s), 0)
        cols = lax.broadcasted_iota(jnp.int32, (s, s), 1)
        scores = jnp.where((rows >= cols)[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0:
        bh = lax.broadcasted_iota(jnp.int32, (b, nh, 1, 1), 0) * nh \
            + lax.broadcasted_iota(jnp.int32, (b, nh, 1, 1), 1)
        rows = lax.broadcasted_iota(jnp.int32, (1, 1, s, 1), 2)
        cols = lax.broadcasted_iota(jnp.int32, (1, 1, 1, s), 3)
        keep = _keep_mask(seed.astype(jnp.uint32), bh, rows, cols,
                          dropout_p)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def fused_attention_block(x, qkv_w, qkv_b, out_w, out_b, ln_scale, ln_bias,
                          *, num_heads: int, causal: bool = True,
                          epsilon: float = 1e-5, attn_dropout: float = 0.0,
                          hidden_dropout: float = 0.0, rotary: bool = False,
                          rope_base: float = 10000.0,
                          scale: Optional[float] = None,
                          training: bool = True, seed=None):
    """The attention half of a pre-LN decoder block as one fused op:

        out = x + drop(W_out · FMHA(rope?(split(W_qkv · LN(x) + b))) + b)

    On TPU this chains the K1 ln_linear kernel, the flash-attention Pallas
    kernel (in-kernel attention dropout), and the K2 epilogue kernel; each
    segment's custom_vjp recomputes through the flash bwd kernels, so the
    only saved activations are the residual stream and the flash lse.
    Off-TPU the pure-jnp composition (same hash-dropout streams) runs —
    the tier-1 oracle.  ``qkv_w`` is (h, 3h) in head-major column order
    (head0: q|k|v, head1: …), the GPTAttention layout."""
    x = _arr(x)
    b, s, hidden = x.shape
    enforce(hidden % num_heads == 0,
            f"hidden {hidden} not divisible by num_heads {num_heads}")
    head_dim = hidden // num_heads
    if scale is None:
        scale = head_dim ** -0.5
    if not training:
        attn_dropout = hidden_dropout = 0.0
    seed = _seed_or_draw(seed, attn_dropout > 0.0 or hidden_dropout > 0.0)

    qkv = fused_ln_linear(x, qkv_w, qkv_b, ln_scale, ln_bias,
                          epsilon=epsilon)
    q, k, v = _split_heads(qkv.reshape(b * s, -1), b, s, num_heads,
                           head_dim)
    if rotary:
        q, k = _apply_rope(q, k, rope_base)

    use_flash = (fused_block_route() == "pallas"
                 and head_dim % 8 == 0 and s % 8 == 0)
    if use_flash:
        out = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, scale=scale,
            dropout_p=attn_dropout, training=training, seed=seed)
        out = out.transpose(0, 2, 1, 3)
    else:
        out = _attention_ref(q, k, v, scale, causal, attn_dropout, seed)

    return fused_linear_residual(
        out.reshape(b, s, hidden), out_w, out_b, x,
        dropout_p=hidden_dropout, training=training, seed=seed,
        salt=_SALT_RESID)


def fused_attention_block_kvcache(x, qkv_w, qkv_b, out_w, out_b, ln_scale,
                                  ln_bias, k_buf, v_buf, used, *,
                                  num_heads: int, epsilon: float = 1e-5,
                                  scale: Optional[float] = None,
                                  rotary: bool = False,
                                  rope_base: float = 10000.0):
    """Decode-step rendering of :func:`fused_attention_block` against a
    fixed-shape KV cache (reference CacheKV / fused_multi_transformer
    decode): fused LN→QKV, cache write at ``used``, streaming cache
    attention (the flash decode kernel on TPU — dynamic trip count, one
    compile for every position), fused out-proj+residual.  Inference-only
    (no dropout).  Returns ``(out, k_buf, v_buf)``."""
    x = _arr(x)
    b, s, hidden = x.shape
    head_dim = hidden // num_heads
    if scale is None:
        scale = head_dim ** -0.5
    qkv = fused_ln_linear(x, qkv_w, qkv_b, ln_scale, ln_bias,
                          epsilon=epsilon)
    q, k, v = _split_heads(qkv.reshape(b * s, -1), b, s, num_heads,
                           head_dim)
    if rotary:
        q, k = _apply_rope(q, k, rope_base)
    q = q.transpose(0, 2, 1, 3)                       # (b, heads, s, d)
    k_buf = lax.dynamic_update_slice(
        k_buf, k.transpose(0, 2, 1, 3).astype(k_buf.dtype), (0, 0, used, 0))
    v_buf = lax.dynamic_update_slice(
        v_buf, v.transpose(0, 2, 1, 3).astype(v_buf.dtype), (0, 0, used, 0))
    L = k_buf.shape[2]
    if (fused_block_route() == "pallas" and s == 1 and L % 8 == 0
            and head_dim % 8 == 0):
        out = flash_attention_kvcache(q, k_buf, v_buf, used + 1,
                                      scale=scale)
    else:
        rows = used + jnp.arange(s)
        cols = jnp.arange(L)
        scores = (jnp.einsum("bhqd,bhkd->bhqk", q, k_buf)
                  * scale).astype(jnp.float32)
        valid = cols[None, :] <= rows[:, None]
        scores = jnp.where(valid[None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_buf.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_buf)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, hidden)
    y = fused_linear_residual(out, out_w, out_b, x, dropout_p=0.0,
                              training=False)
    return y, k_buf, v_buf
