"""Nemotron-3-Super (``model_type: nemotron_h``;
huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16) for the serving
engine: Mamba-2 layers that keep a recurrent state a sequence,
grouped-query attention layers, and expert layers whose routed experts work
in a narrower latent, each layer a mixer OR a feed-forward alone.

``hybrid_override_pattern`` names each layer: ``M`` Mamba-2, ``*``
attention, ``E`` an expert layer.  With ``x`` the residual stream and ``h =
RMSNorm(x)``, every layer is ``x <- x + f(h)`` (``models/decoder_stack.py``'s
:class:`OneBranchLayer`), no bias anywhere but the convolution's:

- ``M``: ``[z | xBC | dt] = h W_in`` (widths ``heads x head_dim``, that plus
  ``2 x n_groups x ssm_state_size``, ``heads``); ``xBC <- SiLU(causal
  depthwise conv of conv_kernel over xBC + b_conv)``; ``[x | B | C] = xBC``;
  per head ``n``, reading group ``n // (heads / n_groups)``: ``dt =
  softplus(dt_n + dt_bias_n)``, ``A = -exp(A_log_n)``, ``S_t = exp(dt A)
  S_{t-1} + dt x_t (outer) B_t``, ``y_t = S_t C_t + D_n x_t``
  (``inference/ssm_state.py``); ``f(h) = GroupRMSNorm(y * SiLU(z)) W_out``,
  the norm after the gate and over ``n_groups`` groups.
- ``*``: ``q = h W_q`` in ``num_heads`` heads, ``k = h W_k`` / ``v = h W_v``
  in ``num_kv_heads``, all of ``head_dim``; causal softmax at ``head_dim^
  -0.5``, query head ``j`` reading key/value head ``j // (num_heads /
  num_kv_heads)``; no rotary (the family's modeling code applies none);
  through ``W_o``.
- ``E``: :class:`paddle_tpu.nn.DroplessMoE` with a sigmoid, bias-corrected
  router over ``n_routed_experts`` at full width, ``relu(u W_up)^2 W_down``
  experts on ``u = h W_latent_down`` (``moe_latent_size``) whose weighted
  sum goes out through ``W_latent_up``, and one shared expert ``relu(h
  W_up)^2 W_down`` of ``moe_shared_expert_intermediate_size`` at full
  width.

The cache: a Mamba layer keeps, a sequence, its convolution's last
``conv_kernel`` inputs and its float32 state ``(heads, head_dim,
ssm_state_size)`` in a slot of the state kind (``kv_cache.StateLayer``);
an attention layer keeps its keys and values as flat rows in pages, as
MiMo-V2's full layers do; an expert layer keeps nothing.  Decode steps a
slot's state in place (``ssm_state_decode``) and runs ``gqa_full_decode``;
prefill runs the chunked scan from the slot's state (zeros at position 0)
and the attention's blocked form on the chunk.

Left out: the multi-token-prediction layer (``num_nextn_predict_layers``;
a step that yields more than one token is ROADMAP B8).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.errors import enforce
from ..inference.gqa_attention import gqa_decode, gqa_prefill_attention
from ..inference.kv_cache import StateLayer
from ..inference.ssm_state import (causal_conv, conv_step, initial_state,
                                   ssm_chunk_scan, ssm_state_decode,
                                   ssm_tail_read)
from ..nn import initializer as I
from ..nn.dropless_moe import DroplessMoE
from ..nn.layer import Layer
from .decoder_stack import DecoderForCausalLM, OneBranchLayer, rms_norm

__all__ = ["NemotronHConfig", "NemotronHMamba", "NemotronHAttention",
           "NemotronHForCausalLM", "nemotron_h_tiny", "MAMBA", "ATTENTION",
           "EXPERTS"]

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
_PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
              "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    hybrid_override_pattern: str = _PUBLISHED
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512        # the router's width, whatever is held
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"
    ep_degree: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        enforce(set(self.hybrid_override_pattern) <= {MAMBA, ATTENTION,
                                                      EXPERTS},
                f"pattern {self.hybrid_override_pattern!r}: M, * and E only")
        enforce(self.mamba_num_heads % self.n_groups == 0
                and self.num_heads % self.num_kv_heads == 0,
                "heads over groups, query heads over key/value heads")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def kind(self, layer: int) -> str:
        return self.hybrid_override_pattern[layer]


class NemotronHMamba(Layer):
    """One Mamba-2 mixer."""

    def __init__(self, config):
        super().__init__()
        c = self.config = config
        init = I.NormalInDtype(c.initializer_range)
        heads = c.mamba_num_heads
        self.w_in = self.create_parameter(
            (c.hidden_size, c.d_inner + c.conv_dim + heads), c.dtype, init)
        self.conv_w = self.create_parameter(
            (c.conv_kernel, c.conv_dim), c.dtype,
            I.NormalInDtype(c.conv_kernel ** -0.5))
        self.conv_b = self.create_parameter((c.conv_dim,), c.dtype,
                                            I.NormalInDtype(0.1))
        # the family's initialisation, drawn from the stream: dt between
        # time_step_min and _max (log-uniform, floored), dt_bias its
        # inverse softplus; A in [1, 16]; D about 1 (published: ones, here
        # spread so that a head's own D is tested)
        u = I.Uniform(0.0, 1.0)
        self.dt_bias = self.create_parameter((heads,), "float32", u)
        self.A_log = self.create_parameter((heads,), "float32", u)
        self.D = self.create_parameter((heads,), "float32",
                                       I.Normal(1.0, 0.2))
        lo, hi = np.log(c.time_step_min), np.log(c.time_step_max)
        dt = jnp.maximum(jnp.exp(self.dt_bias.value * (hi - lo) + lo),
                         c.time_step_floor)
        self.dt_bias.set_value(dt + jnp.log(-jnp.expm1(-dt)))
        self.A_log.set_value(jnp.log(1.0 + 15.0 * self.A_log.value))
        self.norm = self.create_parameter((c.d_inner,), c.dtype,
                                          I.Constant(1.0))
        self.w_out = self.create_parameter((c.d_inner, c.hidden_size),
                                           c.dtype, init)

    def forward(self, h, positions, cache=None, last_index=None):
        """``h (b, s, hidden)`` normed input.  With a ``StateLayerCache``:
        a decode step (``s == 1``) steps each live row's slot in place; a
        chunk scans from its slot's state (zeros where it starts at
        position 0) and leaves the state at its last real token there.
        Returns ``(y, cache, extra)``; ``extra`` counts the rows whose
        state moved and those that started from zeros."""
        c = self.config
        b, s, _ = h.shape
        heads, p, n, g = (c.mamba_num_heads, c.mamba_head_dim,
                          c.ssm_state_size, c.n_groups)
        # tokens as rows of 2-D arrays: a decode step's (b, 1, width) arrays
        # would be laid out a row a tile
        zxd = h.reshape(b * s, -1) @ self.w_in.value
        z, xbc, dt = jnp.split(zxd, [c.d_inner, c.d_inner + c.conv_dim],
                               axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias.value)
        a = -jnp.exp(self.A_log.value)
        bias = self.conv_b.value
        if cache is None:
            real = jnp.full((b,), s, jnp.int32)
            fresh = jnp.ones((b,), bool)
            tails = states = slots = None
        else:
            tails, states = cache.pages
            slots = cache.slots
            real = jnp.clip(cache.seq_lens - positions[:, 0], 0, s)
            fresh = positions[:, 0] == 0
        live = real > 0

        def split(v):
            return (v[..., :c.d_inner].reshape(v.shape[:-1] + (heads, p)),
                    v[..., c.d_inner:c.d_inner + g * n].reshape(
                        v.shape[:-1] + (g, n)),
                    v[..., c.d_inner + g * n:].reshape(v.shape[:-1] + (g, n)))

        if cache is not None and s == 1:
            tail0 = ssm_tail_read(tails, slots)
            with jax.named_scope("ssm.conv"):
                xbc, tail = conv_step(xbc, tail0, self.conv_w.value, bias)
            x, bm, cm = split(xbc)
            with jax.named_scope("ssm.decode"):
                y, states, tails = ssm_state_decode(
                    states, tails, tail, slots, cache.seq_lens, x, dt, a, bm,
                    cm, self.D.value)
        else:
            xbc, dt = xbc.reshape(b, s, -1), dt.reshape(b, s, -1)
            if cache is None:
                tail0 = jnp.zeros((b, c.conv_kernel, c.conv_dim),
                                  xbc.dtype)
                state0 = jnp.zeros((b, heads, p, n), jnp.float32)
            else:
                tail0, state0 = initial_state(tails, states, slots, fresh)
            with jax.named_scope("ssm.conv"):
                xbc, tail = causal_conv(xbc, tail0, self.conv_w.value, bias,
                                        real)
            x, bm, cm = split(xbc)
            # padded positions keep the state where the last real one left
            # it
            dt = jnp.where(jnp.arange(s)[None, :, None]
                           < real[:, None, None], dt, 0.0)
            y, final = ssm_chunk_scan(x, dt, a, bm, cm, self.D.value,
                                      state0, c.chunk_size)
            if cache is not None:
                tails = tails.at[slots].set(tail)
                states = states.at[slots].set(final)
        with jax.named_scope("ssm.out"):
            y = y.reshape(b * s, c.d_inner) * jax.nn.silu(
                z.astype(jnp.float32))
            y = rms_norm(y.reshape(b * s, g, c.d_inner // g),
                         jnp.ones((c.d_inner // g,), jnp.float32),
                         c.rms_norm_eps).reshape(b * s, c.d_inner)
            y = (y * self.norm.value.astype(jnp.float32)).astype(h.dtype)
            out = (y @ self.w_out.value).reshape(b, s, -1)
        if cache is not None:
            cache = cache.replace(pages=(tails, states))
        extra = {"rows": jnp.sum(live), "reset": jnp.sum(live & fresh)}
        return out, cache, extra


class NemotronHAttention(Layer):
    """One grouped-query attention layer, no rotary, no bias, no sink."""

    def __init__(self, config):
        super().__init__()
        c = self.config = config
        init = I.NormalInDtype(c.initializer_range)
        d, n_kv = c.head_dim, c.num_kv_heads
        self.q = self.create_parameter((c.hidden_size, c.num_heads * d),
                                       c.dtype, init)
        self.k = self.create_parameter((c.hidden_size, n_kv * d), c.dtype,
                                       init)
        self.v = self.create_parameter((c.hidden_size, n_kv * d), c.dtype,
                                       init)
        self.o = self.create_parameter((c.num_heads * d, c.hidden_size),
                                       c.dtype, init)

    def forward(self, h, positions, cache=None, last_index=None):
        """As ``MimoV2Attention.forward`` with neither rotary nor sink."""
        del positions, last_index
        c = self.config
        b, s, _ = h.shape
        d, n_kv = c.head_dim, c.num_kv_heads
        q = (h @ self.q.value).reshape(b, s, c.num_heads, d)
        k = (h @ self.k.value).reshape(b, s, n_kv, d)
        v = h @ self.v.value
        if cache is not None:
            with jax.named_scope("attn.kv_write"):
                cache = cache.write(k.reshape(b * s, n_kv * d),
                                    v.reshape(b * s, n_kv * d))
        scale = d ** -0.5
        with jax.named_scope("attn.full"):
            if cache is not None and s == 1:
                out = gqa_decode(q[:, 0], cache.pages[0], cache.pages[1],
                                 cache.block_tables, cache.seq_lens, n_kv,
                                 scale)[:, None]
            else:
                lens = (cache.seq_lens if cache is not None
                        else jnp.full((b,), s, jnp.int32))
                v = v.reshape(b, s, n_kv, d)
                out = jnp.stack([gqa_prefill_attention(
                    q[i], k[i], v[i], lens[i], scale) for i in range(b)])
        y = out.reshape(b, s, c.num_heads * d) @ self.o.value
        return y, cache, None


def _expert_layer(c) -> DroplessMoE:
    return DroplessMoE(
        c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
        c.num_experts_per_tok, c.n_group, c.topk_group, c.n_shared_experts,
        c.routed_scaling_factor, c.norm_topk_prob, c.ep_degree, c.ep_rank,
        c.dtype, c.initializer_range, scoring_func=c.scoring_func,
        activation="relu2", latent_size=c.moe_latent_size,
        shared_width=c.moe_shared_expert_intermediate_size)


class NemotronHForCausalLM(DecoderForCausalLM):
    _head_scope = "nemotron.head"

    def __init__(self, config):
        c = config
        enforce(EXPERTS in c.hybrid_override_pattern
                and ATTENTION in c.hybrid_override_pattern,
                "a pattern with an expert and an attention layer")

        def make_layer(i):
            kind = c.kind(i)
            if kind == EXPERTS:
                return OneBranchLayer(c, make_mlp=lambda: _expert_layer(c),
                                      is_moe=True)
            mixer = NemotronHMamba if kind == MAMBA else NemotronHAttention
            return OneBranchLayer(c, make_mixer=lambda: mixer(c))

        super().__init__(c, make_layer)
        at = iter(range(c.num_layers))
        self.cache_layers = [None if c.kind(i) == EXPERTS else next(at)
                             for i in range(c.num_layers)]

    def _mixers(self, kind: str) -> int:
        return self.config.hybrid_override_pattern.count(kind)

    # -- the serving engine's surface ----------------------------------------
    def kv_cache_layout(self):
        """A Mamba layer: its state a sequence, the convolution's tail in
        the model's dtype and the recurrent state in float32; an attention
        layer: a token's keys and values as flat rows; an expert layer
        keeps nothing."""
        c = self.config
        layout = []
        for i in range(c.num_layers):
            if c.kind(i) == MAMBA:
                layout.append(StateLayer(
                    ((c.conv_kernel, c.conv_dim),
                     (c.mamba_num_heads, c.mamba_head_dim,
                      c.ssm_state_size)), (c.dtype, "float32")))
            elif c.kind(i) == ATTENTION:
                n = c.num_kv_heads * c.head_dim
                layout.append(((n,), (n,)))
        return layout

    def serving_gauges(self) -> Dict[str, float]:
        """What a sequence keeps in the Mamba layers whatever its length,
        and what a token keeps in the attention layers."""
        c = self.config
        per_layer = (c.conv_kernel * c.conv_dim
                     * jnp.dtype(c.dtype).itemsize
                     + 4 * c.mamba_num_heads * c.mamba_head_dim
                     * c.ssm_state_size)
        return {
            "serve.ssm_state_bytes_per_seq": float(
                per_layer * self._mixers(MAMBA)),
            "serve.kv_full_bytes_per_token": float(
                2 * c.num_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize
                * self._mixers(ATTENTION))}

    def serving_counts(self, counts, kind: str):
        """The expert layers' counts (:mod:`decoder_stack`); the rows whose
        state a unit moved, summed over the Mamba layers, and the slots it
        reset (sequences that started from zeros); after a decode step the
        rows whose slot held a live state and the cached tokens the
        attention read, summed over rows and attention layers.  The root
        span of a decode step carries ``ssm_rows``: the rows
        ``ssm_state_decode`` stepped, summed over the layers."""
        out = super().serving_counts(counts, kind)
        rows = int(counts["ssm_rows"])
        out["counters"]["serve.ssm_state_rows"] = rows
        out["counters"]["serve.ssm_slots_reset"] = int(counts["ssm_reset"])
        out["attrs"] = {"ssm_rows": rows if kind == "decode" else 0}
        if kind == "decode":
            out["gauges"]["serve.ssm_slots_live"] = float(
                rows // max(1, self._mixers(MAMBA)))
            out["counters"]["serve.attn_full_tokens"] = int(
                counts["attn_full_tokens"])
        return out

    def _aux(self, aux, extras, caches):
        mamba = [e for e in extras if e is not None]
        aux["counts"]["ssm_rows"] = sum(e["rows"] for e in mamba)
        aux["counts"]["ssm_reset"] = mamba[0]["reset"]
        aux["counts"]["attn_full_tokens"] = (
            jnp.sum(caches[0].seq_lens) * self._mixers(ATTENTION))
        return aux


def nemotron_h_tiny(**kw: Any) -> NemotronHConfig:
    """The unit tests' size: every kind of layer, no width worth timing;
    chunks of 8, so a prompt of a few dozen tokens crosses several."""
    base = dict(vocab_size=96, hidden_size=64,
                hybrid_override_pattern="ME*MEM*E", num_heads=8,
                num_kv_heads=2, head_dim=16, mamba_num_heads=8,
                mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                conv_kernel=4, chunk_size=8, moe_intermediate_size=32,
                moe_latent_size=24, moe_shared_expert_intermediate_size=48,
                n_routed_experts=16, num_experts_per_tok=3,
                max_position_embeddings=256)
    base.update(kw)
    return NemotronHConfig(**base)
