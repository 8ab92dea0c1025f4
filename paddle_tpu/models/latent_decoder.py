"""A decoder of latent-attention layers over a latent paged cache with
dense and expert feed-forwards: what DeepSeek-V2 (``deepseek_v2.py``),
GLM-5 (``glm5.py``) and dots3 (``dots3.py``) share.  The model files hold a
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
  selection, the absorbed form over the live latent pages with the entries
  not selected masked out.  Prefill: the chunk in blocks of queries.
- The first ``first_k_dense_replace`` layers' feed-forward is a SwiGLU of
  ``intermediate_size``; the others are expert layers
  (:class:`paddle_tpu.nn.DroplessMoE`, either published router).

A layer reads its widths and mechanisms from its SHAPE,
``config.layer_shape(i)``: a configuration of one kind of layer (DeepSeek-V2,
GLM-5) is its own shape (:class:`LatentShape`); a model whose layers differ
in kind (dots3) gives each a :class:`LatentKind`, with its own
widths, rotary, indexer or none, ``window`` (a window layer: its query at
``i`` attends ``0 <= i - j < window``, its cache is a ``WindowLayer`` pool,
its decode ``mla_window_decode`` and its prefill banded) and
``attention_gate`` (head ``n``'s output times ``sigmoid(h W_g)[n]`` before
``W_o``; ``W_g`` is hidden x heads).

A configuration (or a shape, for what is the layer's) is any object with
the sizes read below, and

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

The attention kernels: ``mla_latent_attn`` and ``mla_window_decode``
(``inference/latent_attention``), ``dsa_index_scores`` and
``dsa_sparse_attn`` (``inference/sparse_attention``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from ..inference.kv_cache import WindowLayer
from ..nn import initializer as I
from ..nn.layer import Layer
from .decoder_stack import (DecoderForCausalLM, DecoderLayer,
                            RMSNorm as _Norm, plain_rotary)

__all__ = ["LatentShape", "LatentKind", "LatentAttention",
           "LatentDecoderLayer", "LatentDecoderForCausalLM", "plain_rotary"]

# heads a pass of the blocked prefill
_PREFILL_HEADS = 16

# the (s, s) float32 scores of `_causal_attention` may stand for this many
# (head, query, key) triples at once; a chunk whose one head passes it goes
# through the blocked prefill instead
_SCORE_BUDGET = 1 << 25


class LatentShape:
    """What a latent layer derives from its sizes.  A configuration of one
    kind of layer is its own shape (:meth:`layer_shape`); a model whose
    layers differ in kind gives each a :class:`LatentKind`."""

    window = None              # a window kind's reach in tokens
    attention_gate = False     # a headwise gate on the attention's output

    def layer_shape(self, layer: int) -> "LatentShape":
        """The widths, rotary, indexer, window and gate of layer
        ``layer``."""
        return self

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The latent row as it is stored: whole lane tiles."""
        return -(-self.latent_width // 128) * 128


@dataclasses.dataclass(frozen=True)
class LatentKind(LatentShape):
    """One kind of latent layer of a model whose layers differ in kind
    (dots3: full layers with an indexer beside window layers of their own
    widths): everything :class:`LatentAttention` reads of a layer but the
    stream's width, norms, type and initialiser, which stay the
    configuration's."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    window: Optional[int] = None
    attention_gate: bool = False

    def rotary(self, positions):
        return plain_rotary(positions, self.qk_rope_head_dim,
                            self.rope_theta)

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


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
    """Multi-head latent attention of one layer, with an indexer, a
    window and an output gate where the layer's shape has them."""

    def __init__(self, config, shape=None):
        """``shape``: the layer's widths and mechanisms (a
        :class:`LatentShape`; default the configuration's own)."""
        super().__init__()
        c = self.config = config
        sh = self.shape = config if shape is None else shape
        init = I.NormalInDtype(c.initializer_range)
        qk = sh.qk_nope_head_dim + sh.qk_rope_head_dim
        self.q_a = self.create_parameter(
            (c.hidden_size, sh.q_lora_rank), c.dtype, init)
        self.q_a_norm = _Norm(sh.q_lora_rank, c.rms_norm_eps, c.dtype)
        self.q_b = self.create_parameter(
            (sh.q_lora_rank, sh.num_heads * qk), c.dtype, init)
        self.kv_a = self.create_parameter(
            (c.hidden_size, sh.latent_width), c.dtype, init)
        self.kv_a_norm = _Norm(sh.kv_lora_rank, c.rms_norm_eps, c.dtype)
        self.kv_b = self.create_parameter(
            (sh.kv_lora_rank,
             sh.num_heads * (sh.qk_nope_head_dim + sh.v_head_dim)),
            c.dtype, init)
        self.o = self.create_parameter(
            (sh.num_heads * sh.v_head_dim, c.hidden_size), c.dtype, init)
        if sh.index_topk:
            enforce(sh.index_head_dim >= sh.qk_rope_head_dim,
                    "the indexer turns qk_rope_head_dim of its dims")
            self.index_q_b = self.create_parameter(
                (sh.q_lora_rank, sh.index_n_heads * sh.index_head_dim),
                c.dtype, init)
            self.index_k = self.create_parameter(
                (c.hidden_size, sh.index_head_dim), c.dtype, init)
            self.index_k_norm = _LayerNorm(sh.index_head_dim, 1e-6, c.dtype)
            self.index_w = self.create_parameter(
                (c.hidden_size, sh.index_n_heads), c.dtype, init)
        if sh.attention_gate:
            self.gate = self.create_parameter(
                (c.hidden_size, sh.num_heads), c.dtype, init)

    def _kv_b(self):
        sh = self.shape
        w = self.kv_b.value.reshape(sh.kv_lora_rank, sh.num_heads,
                                    sh.qk_nope_head_dim + sh.v_head_dim)
        return w[..., :sh.qk_nope_head_dim], w[..., sh.qk_nope_head_dim:]

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
        scored, summed over the rows).

        A window layer's decode reads the window's ring of pages
        (``mla_window_decode``) and its prefill is banded; with a gate,
        head ``n``'s output is multiplied by ``sigmoid(h W_g)[n]`` before
        ``W_o``."""
        sh = self.shape
        b, s, _ = h.shape
        nope, rope = sh.qk_nope_head_dim, sh.qk_rope_head_dim
        r = sh.kv_lora_rank
        decode = cache is not None and s == 1
        blocked = not decode and bool(sh.index_topk
                                      or sh.window is not None
                                      or s * s > _SCORE_BUDGET)
        with jax.named_scope("mla.q"):
            c_q = self.q_a_norm(h @ self.q_a.value)
            cos, sin = sh.rotary(positions)                 # (b, s, rope/2)
            if not blocked:     # the blocked prefill makes q a group at a time
                q = (c_q @ self.q_b.value).reshape(b, s, sh.num_heads,
                                                   nope + rope)
                q_nope = q[..., :nope]
                q_rope = _rotary(q[..., nope:], cos[:, :, None],
                                 sin[:, :, None])
        gate = None
        if sh.attention_gate:
            with jax.named_scope("attn.gate"):
                gate = jax.nn.sigmoid(jnp.dot(
                    h, self.gate.value,
                    preferred_element_type=jnp.float32)).astype(h.dtype)
        index = None
        if sh.index_topk:
            with jax.named_scope("dsa.index_q"):
                q_i = (c_q @ self.index_q_b.value).reshape(
                    b, s, sh.index_n_heads, sh.index_head_dim)
                q_i = _rotary_head(q_i, cos[:, :, None], sin[:, :, None],
                                   rope)
                w_i = h @ self.index_w.value
        with jax.named_scope("mla.kv_write"):
            kv = h @ self.kv_a.value
            c_kv = self.kv_a_norm(kv[..., :r])
            k_r = _rotary(kv[..., r:], cos, sin)
            new = [jnp.concatenate(
                [c_kv, k_r, jnp.zeros((b, s, sh.latent_row - sh.latent_width),
                                      h.dtype)],
                axis=-1).reshape(b * s, sh.latent_row)]
            if cache is not None and not sh.index_topk:
                cache = cache.write(*new)
        if sh.index_topk:
            with jax.named_scope("dsa.index_k_write"):
                k_i = _rotary_head(self.index_k_norm(h @ self.index_k.value),
                                   cos, sin, rope)
                if cache is not None:      # both page arrays, one write
                    cache = cache.write(
                        *new, k_i.reshape(b * s, sh.index_head_dim))
            index = (q_i, k_i, w_i)
        if lens is None:
            lens = (cache.seq_lens if cache is not None
                    else jnp.full((b,), s, jnp.int32))
        if blocked:
            with jax.named_scope("mla.prefill"):
                y, sel = self._blocked_prefill(
                    c_q, (cos, sin), c_kv, k_r, index, lens, last_index,
                    gate)
            return y, cache, None if index is None else {"selected": sel}
        w_k, w_v = self._kv_b()                            # (r, heads, d)
        dsa = None
        if decode:
            with jax.named_scope("mla.decode" if sh.window is None
                                 else "mla.window_decode"):
                q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_k)
                q_row = jnp.concatenate(
                    [q_lat, q_rope[:, 0],
                     jnp.zeros((b, sh.num_heads,
                                sh.latent_row - sh.latent_width), q.dtype)],
                    axis=-1)
                if index is None:
                    from ..inference.latent_attention import latent_attention
                    o_lat = latent_attention(
                        q_row, cache.pages[0], cache.block_tables,
                        cache.seq_lens, r, sh.softmax_scale, sh.window)
                else:
                    o_lat, dsa = self._sparse_decode(q_row, index, cache)
                out = jnp.einsum("bhr,rhd->bhd", o_lat, w_v)[:, None]
        else:
            with jax.named_scope("mla.prefill"):
                k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, w_k)
                v = jnp.einsum("bsr,rhd->bshd", c_kv, w_v)
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(
                        k_r[:, :, None], (b, s, sh.num_heads, rope))],
                    axis=-1)
                out = _causal_attention(
                    jnp.concatenate([q_nope, q_rope], axis=-1), k, v, lens,
                    sh.softmax_scale)
        if gate is not None:
            with jax.named_scope("attn.gate"):
                out = out * gate[..., None]
        y = out.reshape(b, s, sh.num_heads * sh.v_head_dim) @ self.o.value
        return y, cache, dsa

    def _blocked_prefill(self, c_q, turn, c_kv, k_r, index, lens,
                         last_index, gate=None):
        """The plain form on a long chunk, or on any chunk under an
        indexer or a window: blocks of queries (``inference/
        sparse_attention``; a window layer's BANDED, ``inference/
        gqa_attention``), and ``_PREFILL_HEADS`` heads at a time through
        ``W_qb``, the rotary, the up-projections, the attention, the gate
        and ``W_o``, so that nothing of ``(chunk, all heads, head size)``
        stands beside the weights and the pool."""
        from ..inference.gqa_attention import gqa_prefill_attention
        from ..inference.sparse_attention import (
            blocked_attention, dsa_prefill_mask, query_block)
        c, sh = self.config, self.shape
        b, s = c_kv.shape[:2]
        block = query_block(s)
        enforce(s % block == 0, f"chunk {s} in blocks of {block}")
        g = min(_PREFILL_HEADS, sh.num_heads)
        while sh.num_heads % g:
            g -= 1
        groups = sh.num_heads // g
        last = (jnp.zeros((b,), jnp.int32) if last_index is None
                else jnp.broadcast_to(jnp.asarray(last_index, jnp.int32),
                                      (b,)))
        w_k, w_v = self._kv_b()                            # (r, heads, d)
        by_group = lambda w: jnp.moveaxis(
            w.reshape(w.shape[0], groups, g, w.shape[2]), 1, 0)
        w_o = self.o.value.reshape(groups, g * sh.v_head_dim, c.hidden_size)
        w_q = by_group(self.q_b.value.reshape(
            sh.q_lora_rank, sh.num_heads, -1))
        nope = sh.qk_nope_head_dim
        ys, sels = [], []
        for i in range(b):           # rows of a prefill step: one, or a few
            mask = sel = None
            if index is not None:
                mask, sel = dsa_prefill_mask(
                    tuple(a[i] for a in index), lens[i], last[i],
                    sh.index_topk, block)
                sels.append(sel)

            def heads(y, w, i=i, mask=mask):
                wq, wk, wv, wo = w[:4]
                q = jnp.einsum("sr,rhd->shd", c_q[i], wq)
                q = jnp.concatenate(
                    [q[..., :nope],
                     _rotary(q[..., nope:], turn[0][i][:, None],
                             turn[1][i][:, None])], axis=-1)
                k = jnp.concatenate(
                    [jnp.einsum("sr,rhd->shd", c_kv[i], wk),
                     jnp.broadcast_to(k_r[i][:, None],
                                      (s, g, sh.qk_rope_head_dim))], axis=-1)
                v = jnp.einsum("sr,rhd->shd", c_kv[i], wv)
                if sh.window is not None:
                    with jax.named_scope("mla.window_prefill"):
                        out = gqa_prefill_attention(
                            q, k, v, lens[i], sh.softmax_scale, sh.window,
                            block=block)
                else:
                    with jax.named_scope("dsa.attend" if index is not None
                                         else "mla.prefill_blocks"):
                        out = blocked_attention(
                            q, k, v, lens[i], sh.softmax_scale, block, mask)
                if gate is not None:
                    with jax.named_scope("attn.gate"):
                        out = out * w[4][:, :, None]
                return y + jnp.dot(out.reshape(s, -1), wo,
                                   preferred_element_type=jnp.float32), None

            y0 = jnp.zeros((s, c.hidden_size), jnp.float32)
            xs = (w_q, by_group(w_k), by_group(w_v), w_o)
            if gate is not None:               # (groups, s, g) of this row
                xs += (jnp.moveaxis(gate[i].reshape(s, groups, g), 1, 0),)
            y, _ = lax.scan(heads, y0, xs)
            ys.append(y.astype(c_kv.dtype))
        return jnp.stack(ys), (jnp.stack(sels) if sels else None)

    def _sparse_decode(self, q_row, index, cache):
        from ..inference.sparse_attention import (
            dsa_index_scores, dsa_select, dsa_sparse_attn)
        sh = self.shape
        q_i, _, w_i = index
        with jax.named_scope("dsa.index_scores"):
            scores = dsa_index_scores(q_i[:, 0], w_i[:, 0], cache.pages[1],
                                      cache.block_tables, cache.seq_lens)
        with jax.named_scope("dsa.select"):
            sel = dsa_select(scores, sh.index_topk)
        with jax.named_scope("dsa.attend"):
            o_lat = dsa_sparse_attn(q_row, cache.pages[0],
                                    cache.block_tables, cache.seq_lens,
                                    scores, sel, sh.kv_lora_rank,
                                    sh.softmax_scale)
        return o_lat, {"selected": sel.positions,
                       "kept": jnp.sum(sel.counts),
                       "scored": jnp.sum(cache.seq_lens)}


class LatentDecoderLayer(DecoderLayer):
    def __init__(self, config, index: int):
        super().__init__(
            config, lambda: LatentAttention(config,
                                            config.layer_shape(index)),
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
    def _shapes(self):
        c = self.config
        return [c.layer_shape(i) for i in range(c.num_layers)]

    def kv_cache_layout(self):
        """A token a layer: one latent row and, with an indexer, one
        index key; a window layer says how far it reaches."""
        layout = []
        for sh in self._shapes():
            per = ((sh.latent_row,),) + (((sh.index_head_dim,),)
                                         if sh.index_topk else ())
            layout.append(per if sh.window is None
                          else WindowLayer(per, sh.window))
        return layout

    def serving_gauges(self) -> Dict[str, float]:
        c = self.config
        size = jnp.dtype(c.dtype).itemsize
        shapes = self._shapes()
        if any(sh.window for sh in shapes):
            # stored rows: what a token keeps in the full layers, and what
            # a sequence of any length keeps in the window layers
            return {
                "serve.kv_full_bytes_per_token": float(size * sum(
                    sh.latent_row + (sh.index_head_dim if sh.index_topk
                                     else 0)
                    for sh in shapes if sh.window is None)),
                "serve.kv_window_bytes_per_seq": float(size * sum(
                    sh.latent_row * sh.window for sh in shapes
                    if sh.window))}
        out = {"serve.kv_latent_bytes_per_token": float(
            c.latent_width * c.num_layers * size)}
        if c.index_topk:
            out["serve.kv_index_bytes_per_token"] = float(
                c.index_head_dim * c.num_layers * size)
        return out

    def serving_counts(self, counts, kind: str):
        """The expert layers' counts (:mod:`decoder_stack`) and, after a
        decode step, with an indexer the cache entries attended and the
        entries scored, with window layers the tokens their windows held,
        each summed over rows and layers."""
        out = super().serving_counts(counts, kind)
        if kind == "decode" and "dsa_kept" in counts:
            out["counters"]["serve.dsa_selected_tokens"] = int(
                counts["dsa_kept"])
            out["counters"]["serve.dsa_context_tokens"] = int(
                counts["dsa_scored"])
        if kind == "decode" and "attn_window_tokens" in counts:
            out["counters"]["serve.attn_window_tokens"] = int(
                counts["attn_window_tokens"])
        return out

    def _aux(self, aux, dsas, caches):
        """With an indexer: ``aux["per_logit"]``, the positions selected
        by the one query a row whose logits the step returns
        (``dsa_selected (b, layers with an indexer, index_topk)``, -1
        where none): a chunk's every query would be gigabytes; and of a
        decode step the counts ``dsa_kept`` / ``dsa_scored``.  With window
        layers the count ``attn_window_tokens``: ``min(context, window)``
        a row, summed over rows and window layers."""
        if dsas:
            aux["per_logit"] = {"dsa_selected": jnp.stack(
                [d["selected"] for d in dsas], axis=1)}
            if "kept" in dsas[0]:
                aux["counts"]["dsa_kept"] = sum(d["kept"] for d in dsas)
                aux["counts"]["dsa_scored"] = sum(d["scored"] for d in dsas)
        windows = [sh.window for sh in self._shapes() if sh.window]
        if windows:
            lens = caches[0].seq_lens
            aux["counts"]["attn_window_tokens"] = sum(
                jnp.sum(jnp.minimum(lens, w)) for w in windows)
        return aux
