"""GPT-3's decoder, written from the layer equations of Brown et al. 2020
(section 2.1; the GPT-2 block of Radford et al. 2019 with pre-LayerNorm) in
plain ``jax.numpy`` float32: no kernel, no cache, no batching trick, and no
import from ``paddle_tpu``.  It decides ``correct``.

    x_0   = wte[ids] + wpe[0..S)
    a     = LN1(x);  q, k, v = a W_qkv + b_qkv, split into heads of d
    att   = softmax(q k^T / sqrt(d) + causal mask) v
    x     = x + att W_o + b_o
    x     = x + gelu(LN2(x) W_in + b_in) W_out + b_out        (exact erf GELU)
    logits = LN_f(x_L) wte^T                                   (tied head)
    loss  = mean over positions t < S-1 of -log softmax(logits_t)[ids_{t+1}]

Departures from the paper, each forced by what the program under test holds:
the fused ``W_qkv``'s columns are ordered head-major — head 0's q|k|v, then
head 1's — as ``paddle_tpu/models/gpt.py`` lays them out for tensor
parallelism (``qkv_layout="head_major"``); the paper's alternating dense and
locally banded sparse attention is dense everywhere, as in the reference
framework's GPT configs; the vocabulary is padded to 50304.

Parameters arrive in the reference's own names (a builder maps the
program's onto them) and in whatever type the program holds them; every
layer is cast to float32 as it is used, so a bf16 model's reference fits
beside its KV pool.  Matmuls run at ``highest`` precision: on a TPU a float32
matmul is otherwise done in bf16 passes.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _block(x, p, num_heads, eps):
    b, s, h = x.shape
    d = h // num_heads
    a = _ln(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = a @ p["w_qkv"].astype(F32) + p["b_qkv"].astype(F32)
    qkv = qkv.reshape(b, s, num_heads, 3, d)             # head-major columns
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(b, s, h) @ p["w_o"].astype(F32) + p["b_o"].astype(F32)
    m = _ln(x, p["ln2_g"], p["ln2_b"], eps)
    m = _gelu(m @ p["w_in"].astype(F32) + p["b_in"].astype(F32))
    return x + m @ p["w_out"].astype(F32) + p["b_out"].astype(F32)


def hidden_states(params: Dict[str, Any], ids, num_heads: int,
                  eps: float = 1e-5):
    """``(B, S, H)`` float32 output of the final LayerNorm."""
    with jax.default_matmul_precision("highest"):
        s = ids.shape[1]
        x = (params["wte"].astype(F32)[ids]
             + params["wpe"].astype(F32)[:s][None])
        for p in params["layers"]:
            x = _block(x, p, num_heads, eps)
        return _ln(x, params["lnf_g"], params["lnf_b"], eps)


def logits_at(params: Dict[str, Any], ids, positions, num_heads: int,
              eps: float = 1e-5):
    """Logits ``(B, K, V)`` at ``positions`` ``(B, K)`` of each sequence.
    Padding after a sequence's end cannot reach an earlier position through
    the causal mask, so ragged sequences are padded to one length."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, num_heads, eps)
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return picked @ params["wte"].astype(F32).T


def lm_loss(params: Dict[str, Any], ids, num_heads: int, eps: float = 1e-5):
    """Mean next-token cross-entropy of ``ids`` ``(B, S)``: position ``t``
    predicts ``ids[t + 1]``; the last position predicts nothing."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, num_heads, eps)
        logits = h[:, :-1] @ params["wte"].astype(F32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(picked)
