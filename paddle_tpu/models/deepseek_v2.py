"""DeepSeek-V2 (arXiv:2405.04434) for the serving engine (ISSUE 28):
multi-head latent attention over a latent paged cache, and DeepSeekMoE
layers through :class:`paddle_tpu.nn.DroplessMoE`.

Per layer, with ``x`` the residual stream (no biases anywhere):

- ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` ->
  heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = h W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``q_rope`` and ``k_r`` (one vector a token, shared by
  the heads) get YaRN rotary; ``[k_nope | v] = c_kv W_kvb`` per head;
  ``softmax((q_nope . k_nope + q_rope . k_r) * scale)`` causal, times
  ``v``, through ``W_o``.
- The cache keeps ``[c_kv after its norm | k_r after rotary]`` a token a
  layer: ``kv_lora_rank + qk_rope_head_dim`` values, stored in a row
  padded to a multiple of 128 (576 -> 640; ``inference/latent_attention``
  says why).
- **Prefill** computes the plain form above on the chunk.  **Decode**
  uses the absorbed form over the cached rows: ``q_lat = q_nope W_kvb^K``,
  ``score = q_lat . c_kv + q_rope . k_r``, ``o_lat = P c_kv``, ``out =
  o_lat W_kvb^V``.  Two paths, one result (``tests/test_deepseek_v2.py``
  holds them together).
- Layer 0's feed-forward is a SwiGLU of ``intermediate_size``; layers
  ``first_k_dense_replace``.. are expert layers.

The share of a deployment: ``ep_degree`` / ``ep_rank`` go to every expert
layer, which holds ``n_routed_experts / ep_degree`` experts and leaves out
what the others would add; ``vocab_size`` is the rows of the embedding
and of the untied head held here (a sliced vocabulary is a smaller
vocabulary).  No code stands in for the absent chips.

Rotary here turns the pairs ``(2i, 2i+1)`` in place; the published code
first moves the even elements to the front half and the odd ones to the
back and turns ``(i, i + d/2)``.  Queries and keys are permuted alike, so
every score is the same; only the cached ``k_r``'s element order differs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from ..nn import initializer as I
from ..nn.dropless_moe import DroplessMoE, SwiGLU
from ..nn.layer import Layer, LayerList

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM", "yarn_inv_freq",
           "yarn_mscale", "deepseek_v2_tiny"]


@dataclasses.dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_layers: int = 60
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160        # the router's width, whatever is held
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    max_position_embeddings: int = 163840
    initializer_range: float = 0.02
    dtype: str = "float32"
    ep_degree: int = 1
    ep_rank: int = 0

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The latent row as it is stored: whole lane tiles."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies as the published code blends them: the
    extrapolated ``theta^(-2i/dim)`` where a pair turns more than
    ``beta_fast`` times over the original context, those over ``factor``
    where it turns fewer than ``beta_slow`` times, a linear ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / theta ** exponent
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return weight.astype(x.dtype) * y.astype(x.dtype)


def _rotary(x, cos, sin):
    """Turn the pairs ``(2i, 2i+1)`` of ``x (..., dim)`` by ``cos / sin
    (..., dim/2)``."""
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _causal_attention(q, k, v, lens, scale):
    """Plain causal attention of a chunk on itself: ``q, k (b, s, heads,
    d)``, ``v (b, s, heads, dv)``, columns at or past ``lens (b,)`` are
    padding.  Heads go through in groups, so the ``(s, s)`` scores of a
    long chunk never stand for all heads at once."""
    b, s, h, _ = q.shape
    group = max(1, min(h, (1 << 25) // (s * s)))
    while h % group:
        group -= 1
    rows = jnp.arange(s)
    mask = ((rows[None, :] <= rows[:, None])[None]
            & (rows[None, None, :] < lens[:, None, None]))[:, None]

    def heads(args):
        qg, kg, vg = args                               # (b, s, group, d)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qg, kg,
                        preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(vg.dtype), vg)

    def split(x):
        return jnp.moveaxis(x.reshape(b, s, h // group, group, -1), 2, 0)

    out = lax.map(heads, (split(q), split(k), split(v)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h, -1)


class _Norm(Layer):
    def __init__(self, width: int, eps: float, dtype):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter((width,), dtype, I.Constant(1.0))

    def forward(self, x):
        return _rms_norm(x, self.weight.value, self.eps)


class DeepseekV2Attention(Layer):
    """Multi-head latent attention."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        c = self.config = config
        init = I.NormalInDtype(c.initializer_range)
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_a = self.create_parameter(
            (c.hidden_size, c.q_lora_rank), c.dtype, init)
        self.q_a_norm = _Norm(c.q_lora_rank, c.rms_norm_eps, c.dtype)
        self.q_b = self.create_parameter(
            (c.q_lora_rank, c.num_heads * qk), c.dtype, init)
        self.kv_a = self.create_parameter(
            (c.hidden_size, c.latent_width), c.dtype, init)
        self.kv_a_norm = _Norm(c.kv_lora_rank, c.rms_norm_eps, c.dtype)
        self.kv_b = self.create_parameter(
            (c.kv_lora_rank,
             c.num_heads * (c.qk_nope_head_dim + c.v_head_dim)),
            c.dtype, init)
        self.o = self.create_parameter(
            (c.num_heads * c.v_head_dim, c.hidden_size), c.dtype, init)

    def _kv_b(self):
        c = self.config
        w = self.kv_b.value.reshape(c.kv_lora_rank, c.num_heads,
                                    c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def forward(self, h, positions, cache=None, lens=None):
        """``h (b, s, hidden)`` normed input, ``positions (b, s)``.  With
        a ``PagedLayerCache``: writes the chunk's latent rows, then the
        absorbed form over the pages (``s == 1``) or the plain form on
        the chunk; returns ``(y, cache)``.  Without: the plain form on
        the whole sequence (``lens``: real tokens a row, default all)."""
        c = self.config
        b, s, _ = h.shape
        nope, rope, r = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
        with jax.named_scope("mla.q"):
            q = self.q_a_norm(h @ self.q_a.value) @ self.q_b.value
            q = q.reshape(b, s, c.num_heads, nope + rope)
            angle = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(
                rope, c.rope_theta, c.rope_factor,
                c.rope_original_max_position_embeddings, c.rope_beta_fast,
                c.rope_beta_slow)
            m = (yarn_mscale(c.rope_factor, c.rope_mscale)
                 / yarn_mscale(c.rope_factor, c.rope_mscale_all_dim))
            cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m   # (b, s, r/2)
            q_nope = q[..., :nope]
            q_rope = _rotary(q[..., nope:], cos[:, :, None], sin[:, :, None])
        with jax.named_scope("mla.kv_write"):
            kv = h @ self.kv_a.value
            c_kv = self.kv_a_norm(kv[..., :r])
            k_r = _rotary(kv[..., r:], cos, sin)
            if cache is not None:
                pad = jnp.zeros((b, s, c.latent_row - c.latent_width),
                                h.dtype)
                cache = cache.write(jnp.concatenate(
                    [c_kv, k_r, pad], axis=-1).reshape(b * s, c.latent_row))
        w_k, w_v = self._kv_b()                            # (r, heads, d)
        if cache is not None and s == 1:
            from ..inference.latent_attention import latent_attention
            with jax.named_scope("mla.decode"):
                q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_k)
                q_row = jnp.concatenate(
                    [q_lat, q_rope[:, 0],
                     jnp.zeros((b, c.num_heads,
                                c.latent_row - c.latent_width), q.dtype)],
                    axis=-1)
                o_lat = latent_attention(
                    q_row, cache.pages[0], cache.block_tables,
                    cache.seq_lens, r, c.softmax_scale)
                out = jnp.einsum("bhr,rhd->bhd", o_lat, w_v)[:, None]
        else:
            with jax.named_scope("mla.prefill"):
                if lens is None:
                    lens = (cache.seq_lens if cache is not None
                            else jnp.full((b,), s, jnp.int32))
                k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, w_k)
                v = jnp.einsum("bsr,rhd->bshd", c_kv, w_v)
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(
                        k_r[:, :, None], (b, s, c.num_heads, rope))],
                    axis=-1)
                out = _causal_attention(
                    jnp.concatenate([q_nope, q_rope], axis=-1), k, v, lens,
                    c.softmax_scale)
        y = out.reshape(b, s, c.num_heads * c.v_head_dim) @ self.o.value
        return y, cache


class DeepseekV2DecoderLayer(Layer):
    def __init__(self, config: DeepseekV2Config, index: int):
        super().__init__()
        c = self.config = config
        self.input_norm = _Norm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.attn = DeepseekV2Attention(c)
        self.post_attn_norm = _Norm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.is_moe = index >= c.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.n_group, c.topk_group,
                c.n_shared_experts, c.routed_scaling_factor,
                c.norm_topk_prob, c.ep_degree, c.ep_rank, c.dtype,
                c.initializer_range)
        else:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size, c.dtype,
                              c.initializer_range)

    def forward(self, x, positions, cache=None, valid=None):
        """-> ``(x, cache, aux)``; ``aux`` is None for the dense layer."""
        b, s, hidden = x.shape
        a, cache = self.attn(self.input_norm(x), positions, cache)
        x = x + a
        h = self.post_attn_norm(x)
        if not self.is_moe:
            return x + self.mlp(h), cache, None
        y, aux = self.mlp(h.reshape(b * s, hidden),
                          None if valid is None else valid.reshape(-1))
        aux["topk"] = aux["topk"].reshape(b, s, -1)
        return x + y.reshape(b, s, hidden), cache, aux


class DeepseekV2ForCausalLM(Layer):
    """Embedding, decoder stack, final RMSNorm, untied head — all of
    ``vocab_size`` rows (the slice held here)."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        c = self.config = config
        enforce(c.num_layers > c.first_k_dense_replace >= 0,
                "no expert layer in this depth")
        init = I.NormalInDtype(c.initializer_range)
        self.embed = self.create_parameter(
            (c.vocab_size, c.hidden_size), c.dtype, init)
        self.layers = LayerList([DeepseekV2DecoderLayer(c, i)
                                 for i in range(c.num_layers)])
        self.norm = _Norm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.head = self.create_parameter(
            (c.hidden_size, c.vocab_size), c.dtype, init)

    # -- the serving engine's surface ----------------------------------------
    def kv_cache_layout(self):
        """One latent row a token a layer."""
        return [((self.config.latent_row,),)] * self.config.num_layers

    def serving_gauges(self) -> Dict[str, float]:
        c = self.config
        return {"serve.kv_latent_bytes_per_token": float(
            c.latent_width * c.num_layers * jnp.dtype(c.dtype).itemsize)}

    def serving_counts(self, counts, kind: str):
        """What a step's counts (host copies of ``aux["counts"]``) add to
        the engine's registry: the pairs computed here, the held experts
        that saw a token (summed over the expert layers), the pairs
        dropped (0: the layer is dropless), and after a decode step its
        busiest held expert over the mean, averaged over the layers."""
        load = counts["moe_load"]
        pairs = int(load.sum())
        out = {"counters": {
            "serve.moe_pairs": pairs,
            "serve.moe_experts_touched": int((load > 0).sum()),
            "serve.moe_pairs_dropped": int(counts["moe_dropped"])},
            "gauges": {}}
        if kind == "decode" and pairs:
            out["gauges"]["serve.moe_load_max_over_mean"] = float(
                (load.max(axis=1) / load.mean(axis=1).clip(1e-9)).mean())
        return out

    def _stack(self, input_ids, positions, caches, valid):
        x = jnp.take(self.embed.value, input_ids, axis=0)
        new_caches, auxes = [], []
        for i, layer in enumerate(self.layers):
            x, cache, aux = layer(
                x, positions, None if caches is None else caches[i], valid)
            new_caches.append(cache)
            if aux is not None:
                auxes.append(aux)
        return self.norm(x), new_caches, auxes

    def forward(self, input_ids):
        """Logits ``(b, s, vocab)`` of whole sequences, no cache: the
        plain form of attention throughout."""
        b, s = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        hidden, _, _ = self._stack(input_ids, pos, None, None)
        return hidden @ self.head.value

    def serving_step(self, input_ids, caches, position_offset, last_index):
        """One engine step over latent paged caches: ``(logits (b, vocab),
        new_caches, aux)``.  ``aux["counts"]`` are the expert layers'
        counts for :meth:`serving_counts` (``moe_load (expert layers,
        held)``, ``moe_dropped``), ``aux["per_token"]`` their choices
        (``moe_topk (b, s, expert layers, top_k)``); rows and positions
        past ``seq_lens`` are padding and reach no expert."""
        b, s = input_ids.shape
        off = jnp.asarray(position_offset)
        pos = jnp.broadcast_to(
            (off[:, None] if off.ndim else off) + jnp.arange(s), (b, s))
        lens = caches[0].seq_lens
        valid = ((jnp.arange(s)[None, :] < lens[:, None]) if s > 1
                 else (lens > 0)[:, None])
        hidden, new_caches, auxes = self._stack(input_ids, pos, caches,
                                                valid)
        with jax.named_scope("dsv2.head"):
            idx = jnp.broadcast_to(jnp.asarray(last_index, jnp.int32), (b,))
            logits = hidden[jnp.arange(b), idx] @ self.head.value
        aux = {"counts": {
            "moe_load": jnp.stack([a["load"] for a in auxes]),
            "moe_dropped": sum(a["dropped"] for a in auxes)},
            "per_token": {
                "moe_topk": jnp.stack([a["topk"] for a in auxes], axis=2)}}
        return logits, new_caches, aux


def deepseek_v2_tiny(**kw: Any) -> DeepseekV2Config:
    """The unit tests' size: every mechanism, no width worth timing."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_layers=3, num_heads=4,
                q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                n_shared_experts=2, num_experts_per_tok=3, n_group=4,
                topk_group=2, routed_scaling_factor=16.0,
                max_position_embeddings=256,
                rope_original_max_position_embeddings=32)
    base.update(kw)
    return DeepseekV2Config(**base)
