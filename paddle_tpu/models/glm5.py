"""GLM-5 (``model_type: glm_moe_dsa``; huggingface.co/zai-org/GLM-5) for
the serving engine (ISSUE 32): multi-head latent attention with DeepSeek
sparse attention (an indexer with its own paged key cache, exact
top-``index_topk``, attention over the selected latent rows) and expert
layers behind a sigmoid, bias-corrected router.

The layer, the stack, the cache writes, the decode and prefill paths and
the head are ``models/latent_decoder.py``'s, shared with DeepSeek-V2; this
file holds what is GLM-5's own: its sizes, plain rotary (``rope_theta``
1e6, interleaved pairs) on 64 of 256 query dims, the plain softmax scale,
the indexer's sizes and the router's kind.  Its kernels are
``dsa_index_scores`` and ``dsa_sparse_attn``
(``inference/sparse_attention.py``); ``mla_latent_attn``
(``inference/latent_attention.py``) is the dense neighbour whose body the
second one runs over the selected rows.

Left out: the multi-token-prediction layer (``num_nextn_predict_layers``),
which the language model's logits do not depend on; serving it needs a
step that yields more than one token a sequence (ROADMAP B8).
"""
from __future__ import annotations

import dataclasses
from typing import Any

from .latent_decoder import (LatentDecoderForCausalLM, LatentShape,
                             plain_rotary)

__all__ = ["Glm5Config", "Glm5ForCausalLM", "glm5_tiny"]


@dataclasses.dataclass
class Glm5Config(LatentShape):
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_layers: int = 78
    num_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256        # the router's width, whatever is held
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"      # topk_method noaux_tc
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 3
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 202752
    initializer_range: float = 0.02
    dtype: str = "float32"
    ep_degree: int = 1
    ep_rank: int = 0

    def rotary(self, positions):
        return plain_rotary(positions, self.qk_rope_head_dim,
                            self.rope_theta)

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


class Glm5ForCausalLM(LatentDecoderForCausalLM):
    _head_scope = "glm5.head"


def glm5_tiny(**kw: Any) -> Glm5Config:
    """The unit tests' size: every mechanism, no width worth timing;
    ``index_topk`` 8, so a sequence of a few dozen tokens selects."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_layers=3, num_heads=4,
                q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=24, index_n_heads=4,
                index_head_dim=16, index_topk=8, n_routed_experts=16,
                n_shared_experts=1, num_experts_per_tok=3,
                first_k_dense_replace=1, max_position_embeddings=256)
    base.update(kw)
    return Glm5Config(**base)
