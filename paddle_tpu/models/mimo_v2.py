"""MiMo-V2-Flash (``model_type: mimo_v2_flash``;
huggingface.co/XiaomiMiMo/MiMo-V2-Flash) for the serving engine (ISSUE 35):
grouped-query attention with keys of 192 and values of 128, window layers
with a learned sink beside full layers, plain rotary on 64 of the 192 dims
with a base a layer kind, a value scale, and expert layers behind a
sigmoid, bias-corrected router with no shared expert.

Per layer ``l`` of kind ``full`` (``hybrid_layer_pattern[l] == 0``) or
``window`` (``1``), ``x`` the residual stream, no bias anywhere:

- ``h = RMSNorm(x)``; ``q = h W_q`` as ``num_heads`` heads of ``head_dim``;
  ``k = h W_k``, ``v = h W_v`` as ``n_kv`` heads of ``head_dim`` and of
  ``v_head_dim``: ``num_kv_heads`` in a full layer, ``swa_num_kv_heads`` in
  a window layer.  Rotary turns the first ``rotary_dim`` dims of ``q`` and
  ``k`` (half-split pairs ``(i, i + rotary_dim / 2)``), base ``rope_theta``
  in full layers and ``swa_rope_theta`` in window layers.  ``v`` is
  multiplied by ``attention_value_scale`` where it is made, so the cache
  holds it scaled.
- Query head ``j`` reads key/value head ``j // (num_heads / n_kv)``; scores
  ``q . k / sqrt(head_dim)`` over ``j <= i``, in a window layer only ``i - j
  < sliding_window``, where each query head also has a learned sink logit in
  its softmax (``inference/gqa_attention.py``).  Through ``W_o``.
- ``moe_layer_freq[l] == 0``: a SwiGLU of ``intermediate_size``; else a
  dropless expert layer (:class:`paddle_tpu.nn.DroplessMoE`).

The cache keeps a token's keys and values as flat rows, ``(n_kv *
head_dim,)`` and ``(n_kv * v_head_dim,)``, and declares each layer's KIND
in :meth:`MimoV2ForCausalLM.kv_cache_layout`: the engine keeps a page pool
and a block table a kind, and a window layer's pool holds only the blocks
its window still reaches (``inference/kv_cache.py``).  Decode runs the
paged kernels ``gqa_full_decode`` / ``gqa_window_decode``; prefill the
blocked and the banded form on the chunk.

The stack, the norms, the feed-forwards, the head and the step's plumbing
are ``models/decoder_stack.py``'s, shared with DeepSeek-V2 and GLM-5.

Left out: the three multi-token-prediction layers (no key of the published
configuration sizes them, and a step that yields more than one token is
ROADMAP B8).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from ..inference.gqa_attention import gqa_decode, gqa_prefill_attention
from ..inference.kv_cache import WindowLayer
from ..nn import initializer as I
from ..nn.layer import Layer
from .decoder_stack import DecoderForCausalLM, DecoderLayer, plain_rotary

__all__ = ["MimoV2Config", "MimoV2Attention", "MimoV2ForCausalLM",
           "mimo_v2_tiny"]

_PERIOD = (0, 1, 1, 1, 1, 1)          # one full layer, five window layers


@dataclasses.dataclass
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_layers: int = 48
    num_heads: int = 64
    head_dim: int = 192
    v_head_dim: int = 128
    num_kv_heads: int = 4
    swa_num_kv_heads: int = 8
    sliding_window: int = 128
    # 0: full attention, 1: window; the published 48 layers
    hybrid_layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1) + _PERIOD * 7 \
        + (0,)
    # 0: dense feed-forward, 1: expert layer
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256        # the router's width, whatever is held
    n_shared_experts: int = 0
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"      # topk_method noaux_tc
    routed_scaling_factor: float = 1.0  # published null
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"
    ep_degree: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(self.moe_layer_freq)
        enforce(len(self.hybrid_layer_pattern) == self.num_layers
                == len(self.moe_layer_freq),
                f"{self.num_layers} layers, patterns of "
                f"{len(self.hybrid_layer_pattern)} and "
                f"{len(self.moe_layer_freq)}")

    @property
    def rotary_dim(self) -> int:
        """``partial_rotary_factor x head_dim``, down to an even number."""
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    def is_window(self, layer: int) -> bool:
        return bool(self.hybrid_layer_pattern[layer])

    def kv_heads(self, layer: int) -> int:
        return (self.swa_num_kv_heads if self.is_window(layer)
                else self.num_kv_heads)


def _rotary_half(x, cos, sin, dim: int):
    """Turn the pairs ``(i, i + dim / 2)`` of the first ``dim`` values of
    ``x (..., width)`` by ``cos / sin (..., dim / 2)``; the rest stay."""
    xf = x.astype(jnp.float32)
    a, b = xf[..., :dim // 2], xf[..., dim // 2:dim]
    return jnp.concatenate(
        [(a * cos - b * sin).astype(x.dtype),
         (b * cos + a * sin).astype(x.dtype), x[..., dim:]], axis=-1)


class MimoV2Attention(Layer):
    """One layer's grouped-query attention, full or window."""

    def __init__(self, config, layer: int):
        super().__init__()
        c = self.config = config
        self.window = c.sliding_window if c.is_window(layer) else None
        self.n_kv = c.kv_heads(layer)
        self.theta = c.swa_rope_theta if self.window else c.rope_theta
        enforce(c.num_heads % self.n_kv == 0 and 0 < c.rotary_dim
                <= c.head_dim, "heads over key/value heads, rotary dims")
        init = I.NormalInDtype(c.initializer_range)
        self.q = self.create_parameter(
            (c.hidden_size, c.num_heads * c.head_dim), c.dtype, init)
        self.k = self.create_parameter(
            (c.hidden_size, self.n_kv * c.head_dim), c.dtype, init)
        self.v = self.create_parameter(
            (c.hidden_size, self.n_kv * c.v_head_dim), c.dtype, init)
        self.o = self.create_parameter(
            (c.num_heads * c.v_head_dim, c.hidden_size), c.dtype, init)
        has_sink = (c.add_swa_attention_sink_bias if self.window
                    else c.add_full_attention_sink_bias)
        # trained; here drawn N(0, 1), so that it takes a weight worth
        # testing beside scores of the same size
        self.sink = (self.create_parameter(
            (c.num_heads,), "float32", I.NormalInDtype(1.0))
            if has_sink else None)

    def forward(self, h, positions, cache=None, last_index=None):
        """``h (b, s, hidden)`` normed input, ``positions (b, s)``.  With
        a ``PagedLayerCache``: writes the chunk's keys and values, then
        the paged kernel (``s == 1``) or the chunk on itself.  Returns
        ``(y, cache, None)``."""
        c = self.config
        b, s, _ = h.shape
        dk, dv, n_kv = c.head_dim, c.v_head_dim, self.n_kv
        scope = "attn.window" if self.window else "attn.full"
        # (the barrier keeps XLA from laying the WEIGHTS out anew every
        # step so that the products come out in heads of 192: it copied
        # 100 MB of `q` a layer a step; now the small products are)
        q, k = lax.optimization_barrier((h @ self.q.value, h @ self.k.value))
        q = q.reshape(b, s, c.num_heads, dk)
        k = k.reshape(b, s, n_kv, dk)
        v = ((h @ self.v.value) * jnp.asarray(c.attention_value_scale,
                                              h.dtype))
        with jax.named_scope("attn.rotary"):
            cos, sin = plain_rotary(positions, c.rotary_dim, self.theta)
            q = _rotary_half(q, cos[:, :, None], sin[:, :, None],
                             c.rotary_dim)
            k = _rotary_half(k, cos[:, :, None], sin[:, :, None],
                             c.rotary_dim)
        if cache is not None:
            with jax.named_scope("attn.kv_write"):
                cache = cache.write(k.reshape(b * s, n_kv * dk),
                                    v.reshape(b * s, n_kv * dv))
        sink = None if self.sink is None else self.sink.value
        scale = dk ** -0.5
        with jax.named_scope(scope):
            if cache is not None and s == 1:
                out = gqa_decode(q[:, 0], cache.pages[0], cache.pages[1],
                                 cache.block_tables, cache.seq_lens, n_kv,
                                 scale, self.window, sink)[:, None]
            else:
                lens = (cache.seq_lens if cache is not None
                        else jnp.full((b,), s, jnp.int32))
                v = v.reshape(b, s, n_kv, dv)
                out = jnp.stack([        # rows of a prefill: one, or a few
                    gqa_prefill_attention(q[i], k[i], v[i], lens[i], scale,
                                          self.window, sink)
                    for i in range(b)])
        y = out.reshape(b, s, c.num_heads * dv) @ self.o.value
        return y, cache, None


class MimoV2ForCausalLM(DecoderForCausalLM):
    _head_scope = "mimo.head"

    def __init__(self, config):
        c = config
        enforce(any(c.moe_layer_freq), "no expert layer in this depth")
        super().__init__(c, lambda i: DecoderLayer(
            c, lambda: MimoV2Attention(c, i), bool(c.moe_layer_freq[i])))

    # -- the serving engine's surface ----------------------------------------
    def kv_cache_layout(self):
        """A token a layer: its keys and its values as flat rows; a window
        layer says how far it reaches."""
        c = self.config
        layout = []
        for i in range(c.num_layers):
            n = c.kv_heads(i)
            shapes = ((n * c.head_dim,), (n * c.v_head_dim,))
            layout.append(WindowLayer(shapes, c.sliding_window)
                          if c.is_window(i) else shapes)
        return layout

    def _kind_bytes(self, window: bool) -> int:
        """What a token keeps over the layers of a kind."""
        c = self.config
        return jnp.dtype(c.dtype).itemsize * sum(
            c.kv_heads(i) * (c.head_dim + c.v_head_dim)
            for i in range(c.num_layers) if c.is_window(i) == window)

    def serving_gauges(self) -> Dict[str, float]:
        """What a token keeps in the full layers, and what a sequence of
        any length keeps in the window layers (its last ``sliding_window``
        tokens; the pool holds whole blocks around them)."""
        return {
            "serve.kv_full_bytes_per_token": float(self._kind_bytes(False)),
            "serve.kv_window_bytes_per_seq": float(
                self._kind_bytes(True) * self.config.sliding_window)}

    def serving_counts(self, counts, kind: str):
        """The expert layers' counts (:mod:`decoder_stack`) and, after a
        decode step, the cached tokens its attention read, summed over
        rows and the layers of each kind."""
        out = super().serving_counts(counts, kind)
        if kind == "decode":
            out["counters"]["serve.attn_full_tokens"] = int(
                counts["attn_full_tokens"])
            out["counters"]["serve.attn_window_tokens"] = int(
                counts["attn_window_tokens"])
        return out

    def _aux(self, aux, extras, caches):
        c = self.config
        lens = caches[0].seq_lens
        windows = sum(c.hybrid_layer_pattern)
        aux["counts"]["attn_full_tokens"] = (
            jnp.sum(lens) * (c.num_layers - windows))
        aux["counts"]["attn_window_tokens"] = (
            jnp.sum(jnp.minimum(lens, c.sliding_window)) * windows)
        return aux


def mimo_v2_tiny(**kw: Any) -> MimoV2Config:
    """The unit tests' size: every mechanism, no width worth timing; a
    window of 8, so a sequence of a few dozen tokens leaves it behind."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_layers=4, num_heads=8,
                head_dim=24, v_head_dim=16, num_kv_heads=2,
                swa_num_kv_heads=4, sliding_window=8,
                hybrid_layer_pattern=(0, 1, 1, 0),
                moe_layer_freq=(0, 1, 1, 1), n_routed_experts=16,
                num_experts_per_tok=3, max_position_embeddings=256)
    base.update(kw)
    return MimoV2Config(**base)
