"""A decoder of latent-attention layers over a latent paged cache with
dense and expert feed-forwards: what DeepSeek-V2 (``deepseek_v2.py``, ISSUE
28) and GLM-5 (``glm5.py``, ISSUE 32) share.  The two model files hold a
configuration each; everything a step computes is here, once.

Per layer, with ``x`` the residual stream (no biases but the index keys'
LayerNorm):

- ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` ->
  heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = h W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``q_rope`` and ``k_r`` (one vector a token, shared by
  the heads) get the configuration's rotary; ``[k_nope | v] = c_kv W_kvb``
  per head; ``softmax((q_nope . k_nope + q_rope . k_r) * scale)`` causal,
  times ``v``, through ``W_o``.
- The cache keeps ``[c_kv after its norm | k_r after rotary]`` a token a
  layer: ``kv_lora_rank + qk_rope_head_dim`` values, stored in a row
  padded to a multiple of 128 (576 -> 640; ``inference/latent_attention``
  says why).
- **Prefill** computes the plain form above on the chunk.  **Decode**
  uses the absorbed form over the cached rows: ``q_lat = q_nope W_kvb^K``,
  ``score = q_lat . c_kv + q_rope . k_r``, ``o_lat = P c_kv``, ``out =
  o_lat W_kvb^V``.  Two paths, one result.
- With an **indexer** (``index_topk > 0``; ``inference/sparse_attention``):
  ``q_idx = c_q W_iq`` -> index heads, ``k_idx = LayerNorm(h W_ik)``, both
  turned by the same rotary on their first ``qk_rope_head_dim`` dims, ``w =
  h W_iw``; a query attends only the ``index_topk`` positions of largest
  ``sum_j w_j ReLU(q_idx_j . k_idx)``.  The index keys are a second,
  narrower page array under the same block table, written with the latent
  rows in one ``cache.write``.  Decode: scores over the live pages, exact
  selection, the absorbed form over the selected rows.  Prefill: the chunk
  in blocks of queries.
- The first ``first_k_dense_replace`` layers' feed-forward is a SwiGLU of
  ``intermediate_size``; the others are expert layers
  (:class:`paddle_tpu.nn.DroplessMoE`, either published router).

A configuration is any object with the sizes read below, and

- ``rotary(positions) -> (cos, sin)``, each ``positions.shape + (rope/2,)``,
  any magnitude correction folded in (YaRN's), and ``softmax_scale``;
- ``index_topk`` (0: no indexer), ``index_n_heads``, ``index_head_dim``;
- ``scoring_func`` (``"softmax"`` / ``"sigmoid"``), ``n_group``,
  ``topk_group``, ``norm_topk_prob``, ``routed_scaling_factor``;
- ``ep_degree`` / ``ep_rank``: the share of a deployment.  Every expert
  layer holds ``n_routed_experts / ep_degree`` experts and leaves out what
  the others would add; ``vocab_size`` is the rows of the embedding and of
  the untied head held here.  No code stands in for the absent chips.

Rotary turns the pairs ``(2i, 2i+1)`` in place; DeepSeek's published code
first moves the even elements to the front half and turns ``(i, i +
d/2)``.  Queries and keys are permuted alike, so every score is the same.

The attention kernels: ``mla_latent_attn`` (``inference/latent_attention``),
``dsa_index_scores`` and ``dsa_sparse_attn``
(``inference/sparse_attention``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from ..nn import initializer as I
from ..nn.layer import Layer
from .decoder_stack import (DecoderForCausalLM, DecoderLayer,
                            RMSNorm as _Norm, plain_rotary)

__all__ = ["LatentShape", "LatentAttention", "LatentDecoderLayer",
           "LatentDecoderForCausalLM", "plain_rotary"]

# heads a pass of the blocked prefill
_PREFILL_HEADS = 16

# the (s, s) float32 scores of `_causal_attention` may stand for this many
# (head, query, key) triples at once; a chunk whose one head passes it goes
# through the blocked prefill instead
_SCORE_BUDGET = 1 << 25


class LatentShape:
    """What both configurations derive from their sizes."""

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The latent row as it is stored: whole lane tiles."""
        return -(-self.latent_width // 128) * 128


def _rotary(x, cos, sin):
    """Turn the pairs ``(2i, 2i+1)`` of ``x (..., dim)`` by ``cos / sin
    (..., dim/2)``."""
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rotary_head(x, cos, sin, dim):
    """Rotary on the first ``dim`` values of ``x (..., width)``."""
    return jnp.concatenate([_rotary(x[..., :dim], cos, sin), x[..., dim:]],
                           axis=-1)


def _causal_attention(q, k, v, lens, scale):
    """Plain causal attention of a chunk on itself: ``q, k (b, s, heads,
    d)``, ``v (b, s, heads, dv)``, columns at or past ``lens (b,)`` are
    padding.  Heads go through in groups, so the ``(s, s)`` scores of a
    chunk never stand for all heads at once; a chunk so long that one
    head's scores pass the budget belongs to
    ``inference.sparse_attention.dsa_prefill_attention`` (blocks of
    queries), where :class:`LatentAttention` sends it."""
    b, s, h, _ = q.shape
    enforce(s * s <= _SCORE_BUDGET,
            f"a chunk of {s} tokens: {s}x{s} scores a head pass the budget "
            f"of {_SCORE_BUDGET}; use the blocked prefill")
    group = max(1, min(h, _SCORE_BUDGET // (s * s)))
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


class _LayerNorm(Layer):
    """The index keys' LayerNorm: weight and bias, statistics in float32."""

    def __init__(self, width: int, eps: float, dtype):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter((width,), dtype, I.Constant(1.0))
        self.bias = self.create_parameter((width,), dtype, I.Constant(0.0))

    def forward(self, x):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + self.eps)
        return (y * self.weight.value.astype(jnp.float32)
                + self.bias.value.astype(jnp.float32)).astype(x.dtype)


class LatentAttention(Layer):
    """Multi-head latent attention, with an indexer where the
    configuration has one."""

    def __init__(self, config):
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
        if c.index_topk:
            enforce(c.index_head_dim >= c.qk_rope_head_dim,
                    "the indexer turns qk_rope_head_dim of its dims")
            self.index_q_b = self.create_parameter(
                (c.q_lora_rank, c.index_n_heads * c.index_head_dim),
                c.dtype, init)
            self.index_k = self.create_parameter(
                (c.hidden_size, c.index_head_dim), c.dtype, init)
            self.index_k_norm = _LayerNorm(c.index_head_dim, 1e-6, c.dtype)
            self.index_w = self.create_parameter(
                (c.hidden_size, c.index_n_heads), c.dtype, init)

    def _kv_b(self):
        c = self.config
        w = self.kv_b.value.reshape(c.kv_lora_rank, c.num_heads,
                                    c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def forward(self, h, positions, cache=None, lens=None, last_index=None):
        """``h (b, s, hidden)`` normed input, ``positions (b, s)``.  With
        a ``PagedLayerCache``: writes the chunk's latent rows (and index
        keys), then the absorbed form over the pages (``s == 1``) or the
        plain form on the chunk; without: the plain form on the whole
        sequence (``lens``: real tokens a row, default all).  Returns
        ``(y, cache, dsa)``; ``dsa`` is None without an indexer, else
        ``selected (b, index_topk)`` (the positions the step's query, or
        the chunk's query at ``last_index``, attended; -1 where none),
        and of a decode step ``kept`` / ``scored`` (entries attended and
        scored, summed over the rows)."""
        c = self.config
        b, s, _ = h.shape
        nope, rope, r = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
        decode = cache is not None and s == 1
        blocked = not decode and bool(c.index_topk
                                      or s * s > _SCORE_BUDGET)
        with jax.named_scope("mla.q"):
            c_q = self.q_a_norm(h @ self.q_a.value)
            cos, sin = c.rotary(positions)                  # (b, s, rope/2)
            if not blocked:     # the blocked prefill makes q a group at a time
                q = (c_q @ self.q_b.value).reshape(b, s, c.num_heads,
                                                   nope + rope)
                q_nope = q[..., :nope]
                q_rope = _rotary(q[..., nope:], cos[:, :, None],
                                 sin[:, :, None])
        index = None
        if c.index_topk:
            with jax.named_scope("dsa.index_q"):
                q_i = (c_q @ self.index_q_b.value).reshape(
                    b, s, c.index_n_heads, c.index_head_dim)
                q_i = _rotary_head(q_i, cos[:, :, None], sin[:, :, None],
                                   rope)
                w_i = h @ self.index_w.value
        with jax.named_scope("mla.kv_write"):
            kv = h @ self.kv_a.value
            c_kv = self.kv_a_norm(kv[..., :r])
            k_r = _rotary(kv[..., r:], cos, sin)
            new = [jnp.concatenate(
                [c_kv, k_r, jnp.zeros((b, s, c.latent_row - c.latent_width),
                                      h.dtype)],
                axis=-1).reshape(b * s, c.latent_row)]
            if cache is not None and not c.index_topk:
                cache = cache.write(*new)
        if c.index_topk:
            with jax.named_scope("dsa.index_k_write"):
                k_i = _rotary_head(self.index_k_norm(h @ self.index_k.value),
                                   cos, sin, rope)
                if cache is not None:      # both page arrays, one write
                    cache = cache.write(
                        *new, k_i.reshape(b * s, c.index_head_dim))
            index = (q_i, k_i, w_i)
        if lens is None:
            lens = (cache.seq_lens if cache is not None
                    else jnp.full((b,), s, jnp.int32))
        if blocked:
            with jax.named_scope("mla.prefill"):
                y, sel = self._blocked_prefill(
                    c_q, (cos, sin), c_kv, k_r, index, lens, last_index)
            return y, cache, None if index is None else {"selected": sel}
        w_k, w_v = self._kv_b()                            # (r, heads, d)
        dsa = None
        if decode:
            with jax.named_scope("mla.decode"):
                q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_k)
                q_row = jnp.concatenate(
                    [q_lat, q_rope[:, 0],
                     jnp.zeros((b, c.num_heads,
                                c.latent_row - c.latent_width), q.dtype)],
                    axis=-1)
                if index is None:
                    from ..inference.latent_attention import latent_attention
                    o_lat = latent_attention(
                        q_row, cache.pages[0], cache.block_tables,
                        cache.seq_lens, r, c.softmax_scale)
                else:
                    o_lat, dsa = self._sparse_decode(q_row, index, cache)
                out = jnp.einsum("bhr,rhd->bhd", o_lat, w_v)[:, None]
        else:
            with jax.named_scope("mla.prefill"):
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
        return y, cache, dsa

    def _blocked_prefill(self, c_q, turn, c_kv, k_r, index, lens,
                         last_index):
        """The plain form on a long chunk, or on any chunk under an
        indexer: blocks of queries (``inference/sparse_attention``), and
        ``_PREFILL_HEADS`` heads at a time through ``W_qb``, the rotary,
        the up-projections, the attention and ``W_o``, so that nothing of ``(chunk, all heads,
        head size)`` stands beside the weights and the pool."""
        from ..inference.sparse_attention import (
            blocked_attention, dsa_prefill_mask, query_block)
        c = self.config
        b, s = c_kv.shape[:2]
        block = query_block(s)
        enforce(s % block == 0, f"chunk {s} in blocks of {block}")
        g = min(_PREFILL_HEADS, c.num_heads)
        while c.num_heads % g:
            g -= 1
        groups = c.num_heads // g
        last = (jnp.zeros((b,), jnp.int32) if last_index is None
                else jnp.broadcast_to(jnp.asarray(last_index, jnp.int32),
                                      (b,)))
        w_k, w_v = self._kv_b()                            # (r, heads, d)
        by_group = lambda w: jnp.moveaxis(
            w.reshape(w.shape[0], groups, g, w.shape[2]), 1, 0)
        w_o = self.o.value.reshape(groups, g * c.v_head_dim, c.hidden_size)
        w_q = by_group(self.q_b.value.reshape(
            c.q_lora_rank, c.num_heads, -1))
        nope = c.qk_nope_head_dim
        ys, sels = [], []
        for i in range(b):           # rows of a prefill step: one, or a few
            mask = sel = None
            if index is not None:
                mask, sel = dsa_prefill_mask(
                    tuple(a[i] for a in index), lens[i], last[i],
                    c.index_topk, block)
                sels.append(sel)

            def heads(y, w, i=i, mask=mask):
                wq, wk, wv, wo = w
                q = jnp.einsum("sr,rhd->shd", c_q[i], wq)
                q = jnp.concatenate(
                    [q[..., :nope],
                     _rotary(q[..., nope:], turn[0][i][:, None],
                             turn[1][i][:, None])], axis=-1)
                k = jnp.concatenate(
                    [jnp.einsum("sr,rhd->shd", c_kv[i], wk),
                     jnp.broadcast_to(k_r[i][:, None],
                                      (s, g, c.qk_rope_head_dim))], axis=-1)
                with jax.named_scope("dsa.attend" if index is not None
                                     else "mla.prefill_blocks"):
                    out = blocked_attention(
                        q, k, jnp.einsum("sr,rhd->shd", c_kv[i], wv), lens[i],
                        c.softmax_scale, block, mask)
                return y + jnp.dot(out.reshape(s, -1), wo,
                                   preferred_element_type=jnp.float32), None

            y, _ = lax.scan(
                heads, jnp.zeros((s, c.hidden_size), jnp.float32),
                (w_q, by_group(w_k), by_group(w_v), w_o))
            ys.append(y.astype(c_kv.dtype))
        return jnp.stack(ys), (jnp.stack(sels) if sels else None)

    def _sparse_decode(self, q_row, index, cache):
        from ..inference.sparse_attention import (
            dsa_index_scores, dsa_select, dsa_slots, dsa_sparse_attn)
        c = self.config
        q_i, _, w_i = index
        with jax.named_scope("dsa.index_scores"):
            scores = dsa_index_scores(q_i[:, 0], w_i[:, 0], cache.pages[1],
                                      cache.block_tables, cache.seq_lens)
        with jax.named_scope("dsa.select"):
            pos, kept = dsa_select(scores, c.index_topk)
            slots = dsa_slots(pos, cache.block_tables, cache.block_size)
        with jax.named_scope("dsa.attend"):
            o_lat = dsa_sparse_attn(q_row, cache.pages[0], slots, kept,
                                    c.kv_lora_rank, c.softmax_scale)
        return o_lat, {"selected": pos, "kept": jnp.sum(kept),
                       "scored": jnp.sum(cache.seq_lens)}


class LatentDecoderLayer(DecoderLayer):
    def __init__(self, config, index: int):
        super().__init__(config, lambda: LatentAttention(config),
                         index >= config.first_k_dense_replace)


class LatentDecoderForCausalLM(DecoderForCausalLM):
    """The stack of :mod:`decoder_stack` over latent-attention layers."""

    _head_scope = "latent.head"

    def __init__(self, config):
        c = config
        enforce(c.num_layers > c.first_k_dense_replace >= 0,
                "no expert layer in this depth")
        super().__init__(c, lambda i: LatentDecoderLayer(c, i))

    # -- the serving engine's surface ----------------------------------------
    def kv_cache_layout(self):
        """A token a layer: one latent row and, with an indexer, one
        index key."""
        c = self.config
        per = ((c.latent_row,),) + (((c.index_head_dim,),) if c.index_topk
                                     else ())
        return [per] * c.num_layers

    def serving_gauges(self) -> Dict[str, float]:
        c = self.config
        size = jnp.dtype(c.dtype).itemsize
        out = {"serve.kv_latent_bytes_per_token": float(
            c.latent_width * c.num_layers * size)}
        if c.index_topk:
            out["serve.kv_index_bytes_per_token"] = float(
                c.index_head_dim * c.num_layers * size)
        return out

    def serving_counts(self, counts, kind: str):
        """The expert layers' counts (:mod:`decoder_stack`) and, with an
        indexer, after a decode step, the cache entries attended and the
        entries scored, summed over rows and layers."""
        out = super().serving_counts(counts, kind)
        if kind == "decode" and "dsa_kept" in counts:
            out["counters"]["serve.dsa_selected_tokens"] = int(
                counts["dsa_kept"])
            out["counters"]["serve.dsa_context_tokens"] = int(
                counts["dsa_scored"])
        return out

    def _aux(self, aux, dsas, caches):
        """With an indexer: ``aux["per_logit"]``, the positions selected
        by the one query a row whose logits the step returns
        (``dsa_selected (b, layers, index_topk)``, -1 where none): a
        chunk's every query would be gigabytes; and of a decode step the
        counts ``dsa_kept`` / ``dsa_scored``."""
        if dsas:
            aux["per_logit"] = {"dsa_selected": jnp.stack(
                [d["selected"] for d in dsas], axis=1)}
            if "kept" in dsas[0]:
                aux["counts"]["dsa_kept"] = sum(d["kept"] for d in dsas)
                aux["counts"]["dsa_scored"] = sum(d["scored"] for d in dsas)
        return aux
