"""dots3-note-prev (``model_type: dots3_note``;
huggingface.co/dots-studio/dots3-note-prev) for the serving engine: latent
attention of TWO kinds in one model, each kind with its own widths, rotary
base and mechanisms, a headwise output gate in both, and expert layers
behind a sigmoid, bias-corrected router.

Per layer, ``x`` the residual stream and ``h = RMSNorm(x)``:

- a ``full_attention`` layer: multi-head latent attention (``q_lora_rank``
  1024, ``kv_lora_rank`` 512, 128 heads of ``qk_nope`` 128 + ``qk_rope`` 64
  and values of 128, rotary base 8e7) under a DeepSeek sparse-attention
  indexer (64 heads of 128, exact top-2048), as GLM-5's;
- a ``sliding_attention`` layer: the same latent form at its own widths
  (``swa_*``: 1024 / 1024, 64 heads of 192 + 64, values of 128, base 5e4),
  no indexer, and a window: the query at ``i`` attends ``j`` with ``0 <= i
  - j < sliding_window_size`` (513: itself and the 512 before it);
- both kinds: head ``n``'s attention output times ``sigmoid(h W_g)[n]``
  before ``W_o`` (``attention_gate_type`` / ``swa_attention_gate_type``
  ``"headwise"``; ``W_g`` is hidden x heads);
- the first ``first_k_dense_replace`` layers' feed-forward is a SwiGLU of
  ``intermediate_size``; the others are expert layers (256 of 1,536, top-8,
  one shared expert, ``noaux_tc``, ``norm_topk_prob``).

Each kind is a :class:`~paddle_tpu.models.latent_decoder.LatentKind`; the
attention, the cache writes, the decode and prefill paths, the stack and
the head are ``models/latent_decoder.py``'s, shared with DeepSeek-V2 and
GLM-5.  The cache keeps, a token, ``[c_kv | k_r]`` in a row of whole lane
tiles and, in a full layer, an index key (640 + 128 values); in a window
layer 1,088 values in a row of 1,152, and only while the window reaches
them: a window layer is a :class:`~paddle_tpu.inference.kv_cache.
WindowLayer`, so the engine keeps a page pool a kind.  Decode runs
``dsa_index_scores`` / ``dsa_sparse_attn`` in full layers and
``mla_window_decode`` (``inference/latent_attention.py``) in window layers;
prefill the indexer's blocked form and the banded form.

Left out: ``apply_mla_qkv_lora_rescale`` (a constant factor on the low-rank
projections, which learned weights and norm gains absorb: 1 here), the
multi-token-prediction layer (no key sizes it; ROADMAP B8) and the vision
and audio towers; the language model's logits depend on none of them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

from ..framework.errors import enforce
from .latent_decoder import LatentDecoderForCausalLM, LatentKind

__all__ = ["Dots3Config", "Dots3ForCausalLM", "dots3_tiny"]

FULL, SLIDING = "full_attention", "sliding_attention"
# the published 46: two full layers, then (S, S, S, F) eleven times
_LAYER_TYPES = (FULL, FULL) + (SLIDING, SLIDING, SLIDING, FULL) * 11


@dataclasses.dataclass
class Dots3Config:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_layers: int = 46
    layer_types: Tuple[str, ...] = _LAYER_TYPES
    sliding_window: int = 513
    # the full kind
    num_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # the sliding kind
    swa_num_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    # the feed-forwards
    n_routed_experts: int = 256        # the router's width, whatever is held
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"      # topk_method noaux_tc
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    initializer_range: float = 0.02
    dtype: str = "float32"
    ep_degree: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        enforce(len(self.layer_types) == self.num_layers
                and set(self.layer_types) <= {FULL, SLIDING},
                f"{self.num_layers} layers, layer_types {self.layer_types}")

    @property
    def full(self) -> LatentKind:
        return LatentKind(
            num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            index_topk=self.index_topk, index_n_heads=self.index_n_heads,
            index_head_dim=self.index_head_dim,
            attention_gate=True)

    @property
    def sliding(self) -> LatentKind:
        return LatentKind(
            num_heads=self.swa_num_heads, q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
            window=self.sliding_window,
            attention_gate=True)

    def layer_shape(self, layer: int) -> LatentKind:
        return self.sliding if self.layer_types[layer] == SLIDING \
            else self.full


class Dots3ForCausalLM(LatentDecoderForCausalLM):
    _head_scope = "dots3.head"


def dots3_tiny(**kw: Any) -> Dots3Config:
    """The unit tests' size: every mechanism, no width worth timing; the
    published period after one leading full layer, a window of 12 and
    ``index_topk`` 8, so a sequence of a few dozen tokens both slides and
    selects (and a selection inside a window would drop some of it)."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_layers=5,
                layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
                sliding_window=12, num_heads=4, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, index_n_heads=4, index_head_dim=16,
                index_topk=8, swa_num_heads=2, swa_q_lora_rank=24,
                swa_kv_lora_rank=32, swa_qk_nope_head_dim=24,
                swa_qk_rope_head_dim=8, swa_v_head_dim=16,
                n_routed_experts=16, num_experts_per_tok=3,
                max_position_embeddings=256)
    base.update(kw)
    return Dots3Config(**base)
