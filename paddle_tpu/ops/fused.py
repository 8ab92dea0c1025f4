"""Fused transformer epilogues + rotary embedding.

Reference semantics:
- fused bias+dropout+residual(+LayerNorm): operators/fused/
  fused_dropout_helper.h `FusedDropoutHelper`:110 (bias+dropout+residual) and
  `FusedDropoutLayerNormHelper`:207 (…+LN) — the epilogue of
  fused_attention_op.cc and fused_feedforward_op.cc.
- fused_feedforward: operators/fused/fused_feedforward_op.cc —
  [pre-LN] → GEMM → act(+dropout) → GEMM → bias+dropout+residual[+post-LN].
- rope: no op in this snapshot (SURVEY §7 spec-vs-snapshot note) —
  BASELINE.json names it for the Pallas set; standard GPT-NeoX rotary
  formulation.

TPU-native design: these are *compositions* — XLA's fusion pass emits the
single fused HBM pass the reference hand-writes in CUDA (cost model: one
read of x/residual, one write), so a hand kernel would only re-derive what
the compiler already does.  Kept as named ops for API parity and so the
fusion boundary is testable (OpTest-style numeric parity in
tests/test_ops.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as fw_random
from ..nn import functional as F


def _arr(x):
    return x.__jax_array__() if hasattr(x, "__jax_array__") else x


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate: float = 0.0, epsilon: float = 1e-5,
        training: bool = True, key=None):
    """out = LayerNorm(residual + dropout(x + bias)) — the reference's
    FusedDropoutLayerNormHelper (fused_dropout_helper.h:207)."""
    x = _arr(x)
    if bias is not None:
        x = x + _arr(bias).astype(x.dtype)
    if dropout_rate > 0.0 and training:
        x = F.dropout(x, dropout_rate, training=True, key=key)
    y = _arr(residual) + x
    return F.layer_norm(y, (y.shape[-1],), ln_scale, ln_bias, epsilon)


def fused_bias_dropout_residual(x, residual, bias=None,
                                dropout_rate: float = 0.0,
                                training: bool = True, key=None):
    """out = residual + dropout(x + bias) (fused_dropout_helper.h:110)."""
    x = _arr(x)
    if bias is not None:
        x = x + _arr(bias).astype(x.dtype)
    if dropout_rate > 0.0 and training:
        x = F.dropout(x, dropout_rate, training=True, key=key)
    return _arr(residual) + x


def fused_feedforward(x, w1, b1, w2, b2, ln_scale=None, ln_bias=None,
                      activation: str = "gelu", dropout1: float = 0.0,
                      dropout2: float = 0.0, epsilon: float = 1e-5,
                      pre_layer_norm: bool = True, training: bool = True):
    """The fused FFN block (fused_feedforward_op.cc): one jit region —
    XLA fuses the activation and dropout into the GEMM epilogues."""
    x = _arr(x)
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, (x.shape[-1],), ln_scale, ln_bias, epsilon)
    act = {"gelu": F.gelu, "relu": F.relu}[activation]
    h = act(F.linear(x, w1, b1))
    if dropout1 > 0.0 and training:
        h = F.dropout(h, dropout1, training=True)
    out = F.linear(h, w2, None)
    out = fused_bias_dropout_residual(out, residual, b2, dropout2, training)
    if not pre_layer_norm:
        out = F.layer_norm(out, (out.shape[-1],), ln_scale, ln_bias, epsilon)
    return out


@functools.lru_cache(maxsize=64)
def _rope_tables(seq_len: int, head_dim: int, base: float):
    """Host-side cache of the rope cos/sin tables per (seq_len, head_dim,
    base) — computed ONCE (eagerly, same f32 jnp expressions the inline
    path used, so numerics are identical) and embedded as trace constants
    thereafter.  Before this cache every layer of every traced step
    rebuilt inv_freq/cos/sin from scratch; now per-layer rope cost is the
    two multiplies (ISSUE 7 satellite)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                          dtype=jnp.float32) / head_dim))
    angles = (jnp.arange(seq_len, dtype=jnp.float32)[:, None]
              * inv_freq)                                # (s, d/2)
    return jnp.cos(angles), jnp.sin(angles)


def rotary_position_embedding(q, k, position_ids=None, base: float = 10000.0):
    """GPT-NeoX-style rotary embedding on (batch, heads, seq, head_dim)
    q/k; rotates the first/second halves of head_dim.  cos/sin come from
    the per-(seq_len, head_dim, base) lru cache when positions are the
    default arange or concrete ids; only traced position_ids fall back to
    the on-the-fly computation."""
    q, k = _arr(q), _arr(k)
    b, h, s, d = q.shape
    ids = _arr(position_ids) if position_ids is not None else None
    if ids is None:
        cos_t, sin_t = _rope_tables(s, d, float(base))
        cos = cos_t[None, None, :, :]                    # (1, 1, s, d/2)
        sin = sin_t[None, None, :, :]
    elif not isinstance(ids, jax.core.Tracer):
        pos = np.asarray(ids)
        cos_t, sin_t = _rope_tables(int(pos.max()) + 1, d, float(base))
        cos = cos_t[pos][:, None, :, :]                  # (b|1, 1, s, d/2)
        sin = sin_t[pos][:, None, :, :]
    else:
        pos = ids
        inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2,
                                              dtype=jnp.float32) / d))
        angles = pos[..., None].astype(jnp.float32) * inv_freq
        cos = jnp.cos(angles)[:, None, :, :]             # (b|1, 1, s, d/2)
        sin = jnp.sin(angles)[:, None, :, :]

    def rot(x):
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        xf1 = x1.astype(jnp.float32)
        xf2 = x2.astype(jnp.float32)
        r1 = xf1 * cos - xf2 * sin
        r2 = xf2 * cos + xf1 * sin
        return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


# ---------------------------------------------------------------------------
# Fused linear + softmax cross-entropy (memory-efficient LM loss).
#
# Reference semantics: the c_softmax_with_cross_entropy objective
# (operators/collective/c_softmax_with_cross_entropy_op.cu) applied to a
# tied-embedding LM head.  The naive composition materializes the full
# [B, S, V] logits **twice** (bf16 matmul output + the f32 softmax
# probabilities XLA saves for backward) — measured on v5e at GPT-125M
# B=8/S=2048 that is ~4.5GB of HLO temps, and B=32 OOMs outright
# (a July reading, to re-measure).  This op never materializes more than
# one [B, chunk, V] block: forward scans over sequence chunks saving only
# the per-token logsumexp; backward recomputes each chunk's logits and
# fuses softmax-grad into the dW / dh matmuls.
# ---------------------------------------------------------------------------
def _lce_chunk(s: int, batch: int = 1, vocab: int = 0):
    """Largest sequence chunk (a multiple of the 128-lane tile) dividing s
    whose per-chunk f32 logits block [batch, chunk, vocab] stays under
    ~1.6GB of HBM (the measured B=32 OOM headroom — batch_scan_125m.json);
    None = sequence too irregular, caller should fall back to the unfused
    path."""
    budget = 1.6e9
    best = None
    for c in (512, 256, 128):
        if s % c == 0:
            best = best or c                   # largest divisor as fallback
            if batch * c * vocab * 4 <= budget:
                return c
    return 128 if best else None               # smallest tile when over budget


def _lce_constraint(logits, spec):
    if spec is None:
        return logits
    from ..distributed.mp_layers import shard_constraint
    return shard_constraint(logits, *spec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _linear_ce(hidden, table, labels, chunk, spec):
    loss, _ = _linear_ce_fwd(hidden, table, labels, chunk, spec)
    return loss


def _lce_split(x, chunk):
    """[b, s, ...] -> [s/chunk, b, chunk, ...] (scan-major)."""
    b, s = x.shape[0], x.shape[1]
    x = x.reshape((b, s // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(x, 1, 0)


def _lce_merge(x):
    """[n, b, chunk, ...] -> [b, n*chunk, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _linear_ce_fwd(hidden, table, labels, chunk, spec):
    vocab = table.shape[0]
    hs = _lce_split(hidden, chunk)
    ls = _lce_split(labels, chunk)

    def body(_, inp):
        hc, lc = inp
        logits = jnp.einsum("bch,vh->bcv", hc, table,
                            preferred_element_type=jnp.float32)
        logits = _lce_constraint(logits, spec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.clip(lc, 0, vocab - 1)[..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        return 0, (lse, picked)

    _, (lse, picked) = jax.lax.scan(body, 0, (hs, ls))
    loss = _lce_merge(lse - picked)
    return loss, _lce_merge(lse)


def _linear_ce_fwd_rule(hidden, table, labels, chunk, spec):
    loss, lse = _linear_ce_fwd(hidden, table, labels, chunk, spec)
    return loss, (hidden, table, labels, lse)


def _linear_ce_bwd_rule(chunk, spec, res, g):
    import numpy as _np
    hidden, table, labels, lse = res
    vocab = table.shape[0]
    hs = _lce_split(hidden, chunk)
    ls = _lce_split(labels, chunk)
    lses = _lce_split(lse, chunk)
    gs = _lce_split(g, chunk)

    def body(dw, inp):
        hc, lc, lsec, gc = inp
        logits = jnp.einsum("bch,vh->bcv", hc, table,
                            preferred_element_type=jnp.float32)
        logits = _lce_constraint(logits, spec)
        p = jnp.exp(logits - lsec[..., None])
        onehot = (lc[..., None] ==
                  jax.lax.broadcasted_iota(lc.dtype, (1, 1, vocab), 2))
        grad = ((p - onehot.astype(p.dtype))
                * gc[..., None].astype(p.dtype)).astype(table.dtype)
        dh = jnp.einsum("bcv,vh->bch", grad, table,
                        preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("bcv,bch->vh", grad, hc,
                             preferred_element_type=jnp.float32)
        return dw, dh.astype(hidden.dtype)

    dw0 = jnp.zeros(table.shape, jnp.float32)
    dw, dhs = jax.lax.scan(body, dw0, (hs, ls, lses, gs))
    dh = _lce_merge(dhs)
    return (dh, dw.astype(table.dtype),
            _np.zeros(labels.shape, jax.dtypes.float0))


_linear_ce.defvjp(_linear_ce_fwd_rule, _linear_ce_bwd_rule)


def linear_softmax_cross_entropy(hidden, table, labels, *,
                                 ignore_index: int = -100,
                                 reduction: str = "mean",
                                 seq_chunk: Optional[int] = None,
                                 logits_spec=None):
    """Cross-entropy of ``softmax(hidden @ table.T)`` against ``labels``
    without materializing full logits (see module note above).

    hidden: (b, s, h); table: (v, h) — e.g. a tied embedding; labels:
    (b, s) int ids, ``ignore_index`` masked out.  ``logits_spec`` optionally
    names mesh axes for the per-chunk logits (e.g. ("dp", None, "mp")) so
    GSPMD keeps the vocab dimension sharded through the scan.  Falls back
    to the unfused path when the sequence has no 128-multiple chunking.
    """
    hidden, table, labels = _arr(hidden), _arr(table), _arr(labels)
    b, s, _ = hidden.shape
    chunk = (seq_chunk if seq_chunk is not None
             else _lce_chunk(s, b, table.shape[0]))
    if chunk is None or s % chunk != 0:
        from ..distributed.mp_ops import parallel_cross_entropy
        logits = jnp.einsum("bsh,vh->bsv", hidden, table)
        return parallel_cross_entropy(
            logits.astype(jnp.float32), labels,
            ignore_index=ignore_index, reduction=reduction)
    spec = tuple(logits_spec) if logits_spec is not None else None
    loss = _linear_ce(hidden, table, labels.astype(jnp.int32), chunk, spec)
    from ..distributed.mp_ops import masked_token_reduce
    return masked_token_reduce(loss, labels != ignore_index, reduction)
