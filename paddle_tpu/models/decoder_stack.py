"""What the decoders of ``latent_decoder.py`` (DeepSeek-V2, GLM-5) and
``mimo_v2.py`` (MiMo-V2-Flash) share, lifted here in ISSUE 35 so that no
model copies it: RMSNorm, plain rotary frequencies, the pre-norm layer
around an attention of the model's own with a SwiGLU or a dropless expert
layer behind it (a long chunk in parts), the stack, the untied head over
the vocabulary slice held here, and the serving step's plumbing (positions,
padding, the one position a row whose logits go out, the expert layers'
counts).

A model gives a layer its attention (``attn(h, positions, cache,
last_index=...) -> (y, cache, extra)``; ``extra`` is whatever the attention
hands out beside its result, or None) and says which layers are expert
layers; it extends :meth:`DecoderForCausalLM._aux` and
:meth:`DecoderForCausalLM.serving_counts` with what its attention counts.

A configuration is any object with ``vocab_size`` (the rows of the
embedding and of the head held here), ``hidden_size``, ``intermediate_size``,
``moe_intermediate_size``, ``num_layers``, ``rms_norm_eps``,
``initializer_range``, ``dtype``, and the router's keys
(``n_routed_experts``, ``num_experts_per_tok``, ``n_group``, ``topk_group``,
``n_shared_experts``, ``routed_scaling_factor``, ``norm_topk_prob``,
``scoring_func``, ``ep_degree`` / ``ep_rank``: the share of a deployment).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..nn import initializer as I
from ..nn.dropless_moe import DroplessMoE, SwiGLU
from ..nn.layer import Layer, LayerList

__all__ = ["RMSNorm", "rms_norm", "plain_rotary", "DecoderLayer",
           "DecoderForCausalLM"]

# an expert layer's row buffer holds every (token, choice) pair and a dense
# layer's gate and up products are twice the stream's width, so a long chunk
# goes through a feed-forward this many tokens at a time
_FFN_TOKENS = 4096


def plain_rotary(positions, dim: int, theta: float):
    """``cos, sin`` of ``positions x theta^(-2i/dim)``."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(angle), jnp.sin(angle)


def rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return weight.astype(x.dtype) * y.astype(x.dtype)


class RMSNorm(Layer):
    def __init__(self, width: int, eps: float, dtype):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter((width,), dtype, I.Constant(1.0))

    def forward(self, x):
        return rms_norm(x, self.weight.value, self.eps)


class DecoderLayer(Layer):
    """``x += attn(norm(x)); x += feed_forward(norm(x))``."""

    def __init__(self, config, make_attn, is_moe: bool):
        """``make_attn()`` builds the layer's attention, called where its
        parameters are drawn: after the input norm's, before the rest."""
        super().__init__()
        c = self.config = config
        self.input_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.attn = make_attn()
        self.post_attn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.is_moe = bool(is_moe)
        if self.is_moe:
            self.mlp = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.n_group, c.topk_group,
                c.n_shared_experts, c.routed_scaling_factor,
                c.norm_topk_prob, c.ep_degree, c.ep_rank, c.dtype,
                c.initializer_range, scoring_func=c.scoring_func)
        else:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size, c.dtype,
                              c.initializer_range)

    def forward(self, x, positions, cache=None, valid=None, last_index=None):
        """-> ``(x, cache, aux, extra)``; ``aux`` is None for a dense
        layer, ``extra`` what the attention handed out."""
        b, s, hidden = x.shape
        a, cache, extra = self.attn(self.input_norm(x), positions, cache,
                                    last_index=last_index)
        x = x + a
        h = self.post_attn_norm(x)
        y, aux = self._feed_forward(
            h.reshape(b * s, hidden),
            None if valid is None else valid.reshape(-1))
        if aux is not None:
            aux["topk"] = aux["topk"].reshape(b, s, -1)
        return x + y.reshape(b, s, hidden), cache, aux, extra

    def _feed_forward(self, h, valid):
        """The layer's feed-forward on ``h (tokens, hidden)`` -> ``(y,
        aux or None)``.  A long chunk goes through ``_FFN_TOKENS`` at a
        time (a 16,384-token chunk's pairs would be gigabytes of rows),
        and only the parts that hold a valid token: a chunk is padded to
        its bucket, and a padding part's result stays zero."""
        one = ((lambda a, v: self.mlp(a, v)) if self.is_moe
               else (lambda a, v: (self.mlp(a), None)))
        t = h.shape[0]
        if t <= _FFN_TOKENS or t % _FFN_TOKENS:
            return one(h, valid)
        parts = t // _FFN_TOKENS
        if valid is None:
            valid = jnp.ones((t,), bool)
        hs = h.reshape(parts, _FFN_TOKENS, -1)
        vs = valid.reshape(parts, _FFN_TOKENS)
        zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                             jax.eval_shape(one, hs[0], vs[0]))
        ys, auxes = lax.map(
            lambda a: lax.cond(jnp.any(a[1]), one, lambda *_: zeros, *a),
            (hs, vs))
        if auxes is None:
            return ys.reshape(t, -1), None
        return ys.reshape(t, -1), {
            "load": jnp.sum(auxes["load"], axis=0),
            "dropped": jnp.sum(auxes["dropped"]),
            "topk": auxes["topk"].reshape(t, -1)}


class DecoderForCausalLM(Layer):
    """Embedding, decoder stack, final RMSNorm, untied head — all of
    ``vocab_size`` rows (the slice held here)."""

    _head_scope = "decoder.head"

    def __init__(self, config, make_layer):
        """``make_layer(i)`` builds layer ``i``, called in order after the
        embedding is drawn."""
        super().__init__()
        c = self.config = config
        init = I.NormalInDtype(c.initializer_range)
        self.embed = self.create_parameter(
            (c.vocab_size, c.hidden_size), c.dtype, init)
        self.layers = LayerList([make_layer(i)
                                 for i in range(c.num_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.head = self.create_parameter(
            (c.hidden_size, c.vocab_size), c.dtype, init)

    def _stack(self, input_ids, positions, caches, valid, last_index=None):
        x = jnp.take(self.embed.value, input_ids, axis=0)
        new_caches, auxes, extras = [], [], []
        for i, layer in enumerate(self.layers):
            x, cache, aux, extra = layer(
                x, positions, None if caches is None else caches[i], valid,
                last_index)
            new_caches.append(cache)
            if aux is not None:
                auxes.append(aux)
            if extra is not None:
                extras.append(extra)
        return self.norm(x), new_caches, auxes, extras

    def forward(self, input_ids):
        """Logits ``(b, s, vocab)`` of whole sequences, no cache: the
        plain form of attention throughout."""
        b, s = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        hidden = self._stack(input_ids, pos, None, None)[0]
        return hidden @ self.head.value

    # -- the serving engine's surface ----------------------------------------
    def serving_counts(self, counts, kind: str) -> Dict[str, Dict]:
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

    def _aux(self, aux, extras, caches):
        """A model's own additions to a step's ``aux`` (``extras``: what
        its attentions handed out, a layer each)."""
        return aux

    def serving_step(self, input_ids, caches, position_offset, last_index):
        """One engine step over paged caches: ``(logits (b, vocab),
        new_caches, aux)``.  ``aux["counts"]`` are the layers' counts for
        :meth:`serving_counts` (``moe_load (expert layers, held)``,
        ``moe_dropped``), ``aux["per_token"]`` the experts chosen
        (``moe_topk (b, s, expert layers, top_k)``); a model adds its own
        in :meth:`_aux`.  Rows and positions past ``seq_lens`` are padding
        and reach no expert."""
        b, s = input_ids.shape
        off = jnp.asarray(position_offset)
        pos = jnp.broadcast_to(
            (off[:, None] if off.ndim else off) + jnp.arange(s), (b, s))
        lens = caches[0].seq_lens
        valid = ((jnp.arange(s)[None, :] < lens[:, None]) if s > 1
                 else (lens > 0)[:, None])
        idx = jnp.broadcast_to(jnp.asarray(last_index, jnp.int32), (b,))
        hidden, new_caches, auxes, extras = self._stack(
            input_ids, pos, caches, valid, idx)
        with jax.named_scope(self._head_scope):
            logits = hidden[jnp.arange(b), idx] @ self.head.value
        aux = {"counts": {
            "moe_load": jnp.stack([a["load"] for a in auxes]),
            "moe_dropped": sum(a["dropped"] for a in auxes)},
            "per_token": {
                "moe_topk": jnp.stack([a["topk"] for a in auxes], axis=2)}}
        return logits, new_caches, self._aux(aux, extras, caches)
