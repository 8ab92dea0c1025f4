"""DeepSeek-V2 (arXiv:2405.04434) for the serving engine (ISSUE 28):
multi-head latent attention over a latent paged cache, and DeepSeekMoE
layers through :class:`paddle_tpu.nn.DroplessMoE`.

The layer, the stack, the cache write, the absorbed decode and the head
are ``models/latent_decoder.py``'s (shared with GLM-5 since ISSUE 32);
this file holds what is DeepSeek-V2's own: its sizes, YaRN rotary on 64 of
192 dims with its magnitude correction, the softmax scale that goes with
it, a softmax group-limited router, no indexer.  Its decode kernel is
``mla_latent_attn`` (``inference/latent_attention.py``); the two
neighbours, ``dsa_index_scores`` and ``dsa_sparse_attn``
(``inference/sparse_attention.py``), belong to models with an indexer.

A chunk of more than 5,792 tokens (``s * s`` over ``2^25``) is prefilled
in blocks of queries (``sparse_attention.dsa_prefill_attention`` without
an indexer) and no longer one head at a time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp

from .latent_decoder import LatentDecoderForCausalLM, LatentShape

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM", "yarn_inv_freq",
           "yarn_mscale", "deepseek_v2_tiny"]


@dataclasses.dataclass
class DeepseekV2Config(LatentShape):
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

    # what `latent_decoder` asks of a configuration beyond its sizes
    index_topk = index_n_heads = index_head_dim = 0       # no indexer
    scoring_func = "softmax"

    def rotary(self, positions):
        """YaRN's ``cos, sin`` at ``positions``, times ``m(factor,
        mscale) / m(factor, mscale_all_dim)``."""
        angle = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_position_embeddings, self.rope_beta_fast,
            self.rope_beta_slow)
        m = (yarn_mscale(self.rope_factor, self.rope_mscale)
             / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))
        return jnp.cos(angle) * m, jnp.sin(angle) * m

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


class DeepseekV2ForCausalLM(LatentDecoderForCausalLM):
    _head_scope = "dsv2.head"


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
