"""GPT: decoder-only transformer for causal-LM pretraining — the north-star
workload (BASELINE.json config #4: GPT-3 1.3B/6.7B hybrid-parallel).

Semantic reference: the fused transformer family the reference builds for
exactly this model — fused_attention_op.cc:221-357 (pre-LN → QKV GEMM → FMHA
→ out proj → bias+dropout+residual), fused_feedforward_op.cc, and the
Megatron TP layers (fleet/meta_parallel/mp_layers.py:30,97,170) this model
instantiates for the hybrid configs.

TPU-first design:
- every Linear is Column/RowParallel with GSPMD PartitionSpecs — serial when
  no mesh, Megatron-TP when fleet.init gives mp>1; no per-rank weight code.
- attention heads shard over mp (qkv column-split = head split);
- activations carry (dp, None, mp-on-hidden) constraints at layer borders —
  the "sequence of sharded GEMMs" layout from the scaling-book recipe;
- dropout keys are counter-based via framework.random.key_scope, TP-safe via
  the RNGStatesTracker fold-in (distributed/random.py);
- optional per-layer recompute (jax.checkpoint) for the 1.3B+ configs;
- logits tied to the embedding table; loss is the vocab-parallel CE
  (c_softmax_with_cross_entropy semantics, distributed/mp_ops.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distributed.fleet.recompute import recompute
from ..distributed.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                     VocabParallelEmbedding, shard_constraint)
from ..distributed.mp_ops import parallel_cross_entropy
from ..framework import random as fw_random
from ..framework.errors import enforce
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.initializer import ParamAttr
from ..nn.layer import Layer, Parameter
from ..nn.layers import Dropout, LayerNorm


def shift_labels(labels, ignore_index: int = -100):
    """Causal-LM label shift: position t is scored against token t+1.

    ``labels`` is the same (B, S) id tensor as the input (the standard
    causal-LM calling convention); the roll keeps the (B, S) shape so
    sp/pp shardings are untouched, and the final position is masked with
    ``ignore_index`` (consumed by parallel_cross_entropy)."""
    shifted = jnp.roll(labels, -1, axis=1)
    return shifted.at[:, -1].set(ignore_index)


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # padded to a multiple of 128 for the MXU
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden_size: Optional[int] = None   # default 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_recompute: bool = False
    recompute_policy: Optional[str] = None
    use_pallas_attention: bool = False   # flash-attention kernel (ops/)
    # block-level fused execution (ISSUE 7, ops/fused_block.py): routes the
    # whole pre-LN block — LN→QKV→attention→out-proj epilogue and
    # LN→GEMM→gelu→GEMM→residual — through the fused kernel surfaces (Pallas
    # on TPU, the jnp composition elsewhere; PTPU_FUSED_BLOCK forces a
    # route).  Train, fixed-shape decode, and paged serving paths all honor
    # it; MoE layers and sp/cp configs stay on the unfused path.
    use_fused_block: bool = False
    dtype: str = "float32"               # activation dtype ("bfloat16" on TPU)
    # long-sequence parallelism over the 'sp' mesh axis (additive TPU-native
    # capability; the reference has none — SURVEY §5):
    #   sequence_parallel: Ulysses-style — activations seq-sharded, heads
    #     resharded over mp×sp inside attention (GSPMD emits the all-to-alls)
    #   context_parallel: ring attention — no device ever holds the full
    #     sequence; KV chunks rotate via ppermute (distributed/
    #     sequence_parallel.py)
    sequence_parallel: bool = False
    context_parallel: bool = False
    # MoE (BASELINE config #5, ERNIE-MoE style): 0 experts = dense FFN.
    # moe_every=2 alternates dense/MoE like GShard; 1 = every layer (needed
    # for the homogeneous-trunk pipeline path).
    moe_num_experts: int = 0
    moe_gate: str = "gshard"
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    moe_every: int = 2
    # memory-efficient LM loss (ops/fused.py linear_softmax_cross_entropy):
    # never materializes the [B, S, V] logits/softmax — measured on v5e this
    # is the top HLO temp of the naive path (a July reading, to re-measure)
    fused_lm_loss: bool = True

    def is_moe_layer(self, index: int) -> bool:
        return (self.moe_num_experts > 0
                and index % self.moe_every == self.moe_every - 1)

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size
        enforce(self.hidden_size % self.num_heads == 0,
                "num_heads must evenly divide hidden_size")
        enforce(not (self.context_parallel and self.attention_dropout > 0),
                "context_parallel (ring attention) does not implement "
                "attention-probability dropout; set attention_dropout=0 "
                "(hidden_dropout is unaffected)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _normal(std):
    return I.Normal(mean=0.0, std=std)


class GPTAttention(Layer):
    """Causal self-attention, TP over heads (qkv column-split = head split,
    reference mp_layers.py usage in the fleet GPT; fused semantics ≙
    fused_attention_op.cc FMHA path)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.config = c
        std = c.initializer_range
        # fused qkv: one (h, 3h) GEMM keeps the MXU busy (reference
        # attn_gemm.h AttnMatMul computes qkv as a single GEMM likewise)
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, gather_output=False,
            weight_attr=ParamAttr(initializer=_normal(std)))
        # GPT-2 style scaled init on residual-out projections
        self.out_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, input_is_parallel=True,
            weight_attr=ParamAttr(
                initializer=_normal(std / math.sqrt(2.0 * c.num_layers))))
        self.attn_dropout_p = c.attention_dropout
        self.resid_dropout = Dropout(c.hidden_dropout)

    def forward(self, x, cache=None):
        from ..distributed.topology import get_mesh
        c = self.config
        b, s, _ = x.shape
        qkv = self.qkv_proj(x)                      # (b, s, 3h) mp-sharded
        # head-major column order (head0: q|k|v, head1: q|k|v, ...): the mp
        # sharding of the fused dim then factors onto `heads`, the outer
        # reshape factor, so GSPMD propagates it through the reshape instead
        # of involuntarily rematerializing (a (3, heads, ...) factorization
        # would need mp | 3)
        qkv = qkv.reshape(b, s, c.num_heads, 3, c.head_dim)
        seq_ax = "sp" if c.sequence_parallel or c.context_parallel else None
        qkv = shard_constraint(qkv, "dp", seq_ax, "mp", None, None)
        q = qkv[:, :, :, 0].transpose(0, 2, 1, 3)   # (b, heads, s, d)
        k = qkv[:, :, :, 1].transpose(0, 2, 1, 3)
        v = qkv[:, :, :, 2].transpose(0, 2, 1, 3)
        if cache is not None:
            from ..inference.kv_cache import PagedLayerCache
            if isinstance(cache, PagedLayerCache):
                # serving path (ISSUE 6): KV lands in shared fixed-size
                # blocks addressed by per-sequence tables; ragged decode
                # batches ride the paged-attention kernel.  Single-host
                # only (pallas_call / the page scatter are opaque to
                # GSPMD) — the serving engine enforces that.
                out, new_cache = self._paged_cache_forward(q, k, v, cache,
                                                          b, s)
                return self.resid_dropout(self.out_proj(out)), new_cache
        if cache is not None:
            # fixed-shape cache (k_buf, v_buf, used): write the new chunk at
            # `used` and attend with an explicit causal+validity mask — no
            # shape growth, so the jitted decode step never retraces
            k_buf, v_buf, used = cache
            k_buf = lax.dynamic_update_slice(
                k_buf, k.astype(k_buf.dtype), (0, 0, used, 0))
            v_buf = lax.dynamic_update_slice(
                v_buf, v.astype(v_buf.dtype), (0, 0, used, 0))
            L = k_buf.shape[2]
            if c.use_pallas_attention and s == 1 and L % 8 == 0 \
                    and c.head_dim % 8 == 0 and get_mesh() is None:
                # single-token decode rides the streaming cache kernel:
                # only blocks holding real entries are read (dynamic trip
                # count on the traced length — reference CacheKV path).
                # Mesh-gated like functional.py's routing: pallas_call is
                # opaque to GSPMD, so sharded decode stays on the
                # partitionable SDPA branch
                from ..ops import flash_attention_kvcache
                out = flash_attention_kvcache(q, k_buf, v_buf, used + 1)
            else:
                rows = used + jnp.arange(s)             # query positions
                cols = jnp.arange(L)
                bias = jnp.where(cols[None, :] <= rows[:, None], 0.0, -1e9)
                out = F.scaled_dot_product_attention(
                    q, k_buf, v_buf,
                    attn_mask=bias[None, None].astype(q.dtype),
                    is_causal=False, dropout_p=0.0, training=False)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, c.hidden_size)
            out = self.resid_dropout(self.out_proj(out))
            return out, (k_buf, v_buf, used + s)
        if c.context_parallel and cache is None:
            # ring attention: seq stays sharded, KV chunks rotate the ring
            from ..distributed.sequence_parallel import (
                ring_attention_sharded)
            mesh = get_mesh()
            if mesh is not None and "sp" in mesh.axis_names:
                out = ring_attention_sharded(q, k, v, causal=True)
            else:  # serial fallback (tests / meshes without an sp axis)
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True,
                    dropout_p=self.attn_dropout_p, training=self.training)
        else:
            if c.sequence_parallel:
                # Ulysses layout change: full seq per shard, heads over
                # mp×sp — the pair of constraints IS the all-to-all pair
                q = shard_constraint(q, "dp", ("mp", "sp"), None, None)
                k = shard_constraint(k, "dp", ("mp", "sp"), None, None)
                v = shard_constraint(v, "dp", ("mp", "sp"), None, None)
            if c.use_pallas_attention and get_mesh() is None:
                # Mosaic refuses to lower a pallas_call inside a GSPMD
                # program ("cannot be automatically partitioned"), which
                # the interpret-mode CPU mesh hides — so, like the decode
                # branch above and functional.py's routing, a mesh takes
                # the partitionable SDPA route until the kernel is
                # wrapped per shard (ROADMAP C6)
                from ..ops import flash_attention
                out = flash_attention(
                    q, k, v, causal=True, dropout_p=self.attn_dropout_p,
                    training=self.training)
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, dropout_p=self.attn_dropout_p,
                    training=self.training)
            if c.sequence_parallel:
                out = shard_constraint(out, "dp", ("mp", "sp"), None, None)
        out = out.transpose(0, 2, 1, 3)             # (b, s, heads, d)
        out = shard_constraint(out, "dp", seq_ax, "mp", None)
        out = out.reshape(b, s, c.hidden_size)
        return self.resid_dropout(self.out_proj(out))

    def _paged_cache_forward(self, q, k, v, cache, b, s):
        """Paged-KV attention (ISSUE 6 serving path).

        Writes this call's k/v into the shared page arrays
        (``PagedLayerCache.write``), then attends:

        - ``s == 1`` (batched decode): ragged paged attention over the
          block tables up to ``seq_lens`` — each row sees its own
          context length (inference/paged_attention.py);
        - ``s > 1`` (prefill chunk): the context IS the chunk (recompute
          prefill after preemption included — the table was freed), so a
          causal in-chunk mask with ``cols < seq_lens`` masking the pad
          columns is exact.
        """
        from ..inference.paged_attention import paged_attention
        c = self.config
        cache = cache.write(
            k.transpose(0, 2, 1, 3).reshape(b * s, c.num_heads, c.head_dim),
            v.transpose(0, 2, 1, 3).reshape(b * s, c.num_heads, c.head_dim))
        if s == 1:
            o = paged_attention(q[:, :, 0, :], cache.k_pages, cache.v_pages,
                                cache.block_tables, cache.seq_lens,
                                block_size=cache.block_size)
            out = o.astype(q.dtype).reshape(b, 1, c.hidden_size)
        else:
            rows = jnp.arange(s)
            cols = jnp.arange(s)
            causal = cols[None, :] <= rows[:, None]              # (s, s)
            valid = cols[None, None, :] < cache.seq_lens[:, None, None]
            bias = jnp.where(causal[None, :, :] & valid, 0.0, -1e9)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=bias[:, None].astype(q.dtype),
                is_causal=False, dropout_p=0.0, training=False)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, c.hidden_size)
        return out, cache

    def fused_paged_forward(self, x, ln, cache):
        """Fused-epilogue serving step (ISSUE 7): LN→QKV as one fused
        kernel pass, the PR 6 paged attention in the middle, out-proj +
        residual as the fused epilogue.  Returns the residual-added block
        output (the caller skips its own ``x + attn(ln(x))``)."""
        from ..ops.fused_block import fused_linear_residual, fused_ln_linear
        c = self.config
        b, s, _ = x.shape
        qkv = fused_ln_linear(x, self.qkv_proj.weight, self.qkv_proj.bias,
                              ln.weight, ln.bias, epsilon=ln.epsilon)
        qkv = qkv.reshape(b, s, c.num_heads, 3, c.head_dim)
        q = qkv[:, :, :, 0].transpose(0, 2, 1, 3)
        k = qkv[:, :, :, 1].transpose(0, 2, 1, 3)
        v = qkv[:, :, :, 2].transpose(0, 2, 1, 3)
        out, new_cache = self._paged_cache_forward(q, k, v, cache, b, s)
        y = fused_linear_residual(out, self.out_proj.weight,
                                  self.out_proj.bias, x,
                                  dropout_p=0.0, training=False)
        return y, new_cache


class GPTMLP(Layer):
    """h → 4h → h, gelu; TP column/row split (reference
    fused_feedforward_op.cc semantics)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.fc_in = ColumnParallelLinear(
            c.hidden_size, c.ffn_hidden_size, gather_output=False,
            weight_attr=ParamAttr(initializer=_normal(c.initializer_range)))
        self.fc_out = RowParallelLinear(
            c.ffn_hidden_size, c.hidden_size, input_is_parallel=True,
            weight_attr=ParamAttr(initializer=_normal(
                c.initializer_range / math.sqrt(2.0 * c.num_layers))))
        self.dropout = Dropout(c.hidden_dropout)

    def forward(self, x):
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x))))


class GPTDecoderLayer(Layer):
    """Pre-LN block (reference fused_attention_op pre_layer_norm=True path +
    fused_feedforward).  With ``config.is_moe_layer(index)`` the FFN is a
    capacity-bucketed MoELayer over the ``ep`` mesh axis (ERNIE-MoE)."""

    def __init__(self, config: GPTConfig, index: int = 0):
        super().__init__()
        c = config
        self.config = c
        self.ln_1 = LayerNorm(c.hidden_size, epsilon=c.layer_norm_epsilon)
        self.attn = GPTAttention(c)
        self.ln_2 = LayerNorm(c.hidden_size, epsilon=c.layer_norm_epsilon)
        self._is_moe = c.is_moe_layer(index)
        if self._is_moe:
            from ..distributed.moe import MoELayer
            self.mlp = MoELayer(
                c.hidden_size, c.ffn_hidden_size, c.moe_num_experts,
                gate=c.moe_gate, capacity_factor=c.moe_capacity_factor,
                dropout_p=c.hidden_dropout,
                weight_attr=ParamAttr(
                    initializer=_normal(c.initializer_range)),
                out_weight_attr=ParamAttr(initializer=_normal(
                    c.initializer_range / math.sqrt(2.0 * c.num_layers))))
        else:
            self.mlp = GPTMLP(c)
        self._use_recompute = c.use_recompute
        self._recompute_policy = c.recompute_policy

    def _fused_block_ok(self) -> bool:
        """use_fused_block eligibility: the fused ops are single-program
        (a pallas_call is opaque to GSPMD — same gating as the flash
        decode kernel) and cover the dense pre-LN block only."""
        c = self.config
        if not c.use_fused_block or self._is_moe:
            return False
        if c.sequence_parallel or c.context_parallel:
            return False
        from ..distributed.topology import get_mesh
        return get_mesh() is None

    def _block_fused(self, x):
        """ISSUE 7 hot path: the two halves of the block as fused ops
        (ops/fused_block.py) — Pallas kernels on TPU, the jnp composition
        as the CPU default and interpret oracle."""
        from ..ops.fused_block import fused_attention_block
        c = self.config
        a = self.attn
        with jax.named_scope("attn"):
            x = fused_attention_block(
                x, a.qkv_proj.weight, a.qkv_proj.bias, a.out_proj.weight,
                a.out_proj.bias, self.ln_1.weight, self.ln_1.bias,
                num_heads=c.num_heads, causal=True,
                epsilon=c.layer_norm_epsilon,
                attn_dropout=c.attention_dropout,
                hidden_dropout=c.hidden_dropout, training=self.training)
        return self._ffn_fused(x), jnp.zeros((), jnp.float32)

    def _ffn_fused(self, x):
        from ..ops.fused_block import fused_ffn_block
        c, m = self.config, self.mlp
        with jax.named_scope("mlp"):
            return fused_ffn_block(
                x, m.fc_in.weight, m.fc_in.bias, m.fc_out.weight,
                m.fc_out.bias, self.ln_2.weight, self.ln_2.bias,
                activation="gelu", dropout2=c.hidden_dropout,
                epsilon=c.layer_norm_epsilon, training=self.training)

    def _fused_cache_forward(self, x, cache):
        """Fused decode step (ISSUE 7): covers both the fixed-shape
        (k_buf, v_buf, used) cache and the PR 6 paged cache."""
        from ..inference.kv_cache import PagedLayerCache
        from ..ops.fused_block import fused_attention_block_kvcache
        c = self.config
        with jax.named_scope("attn"):
            if isinstance(cache, PagedLayerCache):
                x, new_cache = self.attn.fused_paged_forward(x, self.ln_1,
                                                             cache)
            else:
                k_buf, v_buf, used = cache
                a = self.attn
                x, k_buf, v_buf = fused_attention_block_kvcache(
                    x, a.qkv_proj.weight, a.qkv_proj.bias,
                    a.out_proj.weight, a.out_proj.bias, self.ln_1.weight,
                    self.ln_1.bias, k_buf, v_buf, used,
                    num_heads=c.num_heads, epsilon=c.layer_norm_epsilon)
                new_cache = (k_buf, v_buf, used + x.shape[1])
        return self._ffn_fused(x), new_cache

    def _block(self, x):
        """Returns (x, aux): MoE aux losses are collected INSIDE so they
        cross the jax.checkpoint boundary as a real remat output instead of
        leaking a tracer through the thread-local side channel."""
        if self._fused_block_ok():
            return self._block_fused(x)
        from ..distributed.moe import collect_aux_losses
        with collect_aux_losses() as aux_items:
            x, _ = self._attn_mlp(x)
        aux = sum(aux_items) if aux_items else jnp.zeros((), jnp.float32)
        return x, aux

    def _attn_mlp(self, x, cache=None):
        """The unfused block, each part under the scope that names it in
        the trace and in the lowered HLO (``gpt.block/attn`` ...)."""
        with jax.named_scope("ln"):
            h = self.ln_1(x)
        with jax.named_scope("attn"):
            if cache is None:
                h, new_cache = self.attn(h), None
            else:
                h, new_cache = self.attn(h, cache=cache)
            x = x + h
        with jax.named_scope("ln"):
            h = self.ln_2(x)
        with jax.named_scope("mlp"):
            x = x + self.mlp(h)
        return x, new_cache

    def forward(self, x, cache=None):
        with jax.named_scope("gpt.block"):
            return self._forward(x, cache)

    def _forward(self, x, cache):
        from ..distributed.moe import _record_aux
        if cache is not None:
            if self._fused_block_ok():
                return self._fused_cache_forward(x, cache)
            return self._attn_mlp(x, cache)
        if self._use_recompute:
            x, aux = recompute(self._block, x, policy=self._recompute_policy)
        else:
            x, aux = self._block(x)
        if self._is_moe:
            _record_aux(aux)
        return x


class GPTModel(Layer):
    """Embeddings + decoder stack + final LN."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.config = c
        self.wte = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size,
            weight_attr=ParamAttr(initializer=_normal(c.initializer_range)))
        self.wpe = Parameter(_normal(c.initializer_range)(
            fw_random.next_key(),
            (c.max_position_embeddings, c.hidden_size), jnp.float32))
        self.wpe.pspec = P(None, None)
        self.drop = Dropout(c.hidden_dropout)
        from ..nn.layer import LayerList
        self.h = LayerList([GPTDecoderLayer(c, i)
                            for i in range(c.num_layers)])
        self.ln_f = LayerNorm(c.hidden_size, epsilon=c.layer_norm_epsilon)

    def forward(self, input_ids, position_offset: int = 0, caches=None):
        c = self.config
        b, s = input_ids.shape
        # traced-offset form: position_offset may be a traced scalar in the
        # jitted decode step (jnp.arange(traced, ...) would fail); the
        # serving engine passes a (b,) vector — every ragged-batch row
        # decodes at its own position
        off = jnp.asarray(position_offset)
        pos = (off[:, None] + jnp.arange(s) if off.ndim
               else off + jnp.arange(s))
        with jax.named_scope("gpt.embed"):
            x = self.wte(input_ids) + self.wpe.value[pos]
            if c.dtype != "float32":
                x = x.astype(c.dtype)
            x = self.drop(x)
            seq_ax = ("sp" if c.sequence_parallel or c.context_parallel
                      else None)
            x = shard_constraint(x, "dp", seq_ax, None)
        new_caches = []
        for i, layer in enumerate(self.h):
            if caches is not None:
                x, kv = layer(x, cache=caches[i])
                new_caches.append(kv)
            else:
                x = layer(x)
        with jax.named_scope("gpt.ln_f"):
            x = self.ln_f(x)
        if caches is not None:
            return x, new_caches
        return x


class GPTForCausalLM(Layer):
    """LM head tied to the embedding; loss = vocab-parallel softmax CE
    (c_softmax_with_cross_entropy semantics)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)

    def forward(self, input_ids, labels=None):
        from ..distributed.moe import collect_aux_losses
        with collect_aux_losses() as aux_losses:
            hidden = self.gpt(input_ids)        # (b, s, h)
        with jax.named_scope("gpt.head_loss"):
            return self._head_loss(hidden, labels, aux_losses)

    def _head_loss(self, hidden, labels, aux_losses):
        # tied head: logits = h @ wte.T → vocab-sharded over mp
        c = self.config
        table = self.gpt.wte.weight.value.astype(hidden.dtype)
        seq_ax = ("sp" if c.sequence_parallel or c.context_parallel
                  else None)

        def full_logits():
            lg = jnp.einsum("bsh,vh->bsv", hidden, table)
            return shard_constraint(lg, "dp", seq_ax, "mp")

        if labels is None:
            return full_logits()
        shifted = shift_labels(labels)
        from ..distributed.mp_ops import _in_axis
        from ..ops.fused import _lce_chunk, linear_softmax_cross_entropy
        if (c.fused_lm_loss and not _in_axis("mp")
                and _lce_chunk(hidden.shape[1]) is not None):
            # memory-efficient path: loss from (hidden, table) directly —
            # the full [B, S, V] logits are never built (the 16GB-chip
            # budget that makes the full-vocab 1.3B trainable at all),
            # so the logits slot of the return is None; set
            # fused_lm_loss=False to get (loss, logits)
            loss = linear_softmax_cross_entropy(
                hidden, table, shifted,
                logits_spec=("dp", seq_ax, "mp"), reduction="mean")
            logits = None
        else:
            # shard_map vocab-parallel contexts and irregular sequence
            # lengths keep the c_softmax_with_cross_entropy path
            logits = full_logits()
            loss = parallel_cross_entropy(
                logits.astype(jnp.float32), shifted, reduction="mean")
        if aux_losses:
            loss = loss + self.config.moe_aux_weight * sum(aux_losses)
        return loss, logits

    def build_pipeline(self, num_stages: int, num_microbatches: int):
        """Pipeline-parallel wrapper (used by fleet.distributed_model when
        pp_degree > 1; ≙ fleet_base.py:1027 selecting PipelineParallel)."""
        from .gpt_pipeline import GPTPipeline
        return GPTPipeline(self, num_stages, num_microbatches)

    def generate_step(self, input_ids, caches, position_offset: int):
        """Single decode step with KV caches (reference CacheKV path,
        fused_attention_op.cc:235)."""
        hidden, new_caches = self.gpt(
            input_ids, position_offset=position_offset, caches=caches)
        with jax.named_scope("gpt.head"):
            table = self.gpt.wte.weight.value.astype(hidden.dtype)
            logits = jnp.einsum("bsh,vh->bsv", hidden[:, -1:], table)
        return logits, new_caches

    def serving_step(self, input_ids, caches, position_offset, last_index):
        """One serving-engine step over paged caches (ISSUE 6): runs the
        stack, gathers the hidden state at ``last_index`` per row (the
        last *real* token of a padded prefill chunk; 0 for single-token
        decode), and returns its tied-head logits.

        Unlike :meth:`generate_step` this works for ragged padded
        chunks — ``hidden[:, -1]`` of a padded prefill is a pad
        position.  Returns ``(logits (b, vocab), new_caches)``.
        """
        hidden, new_caches = self.gpt(
            input_ids, position_offset=position_offset, caches=caches)
        with jax.named_scope("gpt.head"):
            b = hidden.shape[0]
            idx = jnp.broadcast_to(jnp.asarray(last_index, jnp.int32), (b,))
            h_last = hidden[jnp.arange(b), idx]              # (b, h)
            table = self.gpt.wte.weight.value.astype(h_last.dtype)
            logits = jnp.einsum("bh,vh->bv", h_last, table)
        return logits, new_caches

    def kv_cache_layout(self):
        """What a token keeps in each layer's pages, for the serving
        engine's pool: keys and values of ``(heads, head_dim)``, in whole
        tiles where the decode kernel reads them."""
        from ..inference.paged_attention import page_token_shape
        c = self.config
        slab = page_token_shape(c.num_heads, c.head_dim, c.dtype)
        return [(slab, slab)] * c.num_layers

    def make_caches(self, batch_size: int, max_length: int):
        """Fixed-shape KV caches (one (k_buf, v_buf, used) triple per
        layer) for jitted decoding — preallocated so every decode step has
        identical shapes (no retracing), written via dynamic_update_slice:
        the static-shape rendering of the reference's growing CacheKV."""
        c = self.config
        dt = jnp.dtype(c.dtype) if c.dtype != "float32" else jnp.float32
        shape = (batch_size, c.num_heads, max_length, c.head_dim)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                 jnp.asarray(0, jnp.int32)) for _ in range(c.num_layers)]

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 key=None, eos_token_id: Optional[int] = None):
        """Autoregressive decoding: ONE jitted step (prefill reuses it with
        the prompt chunk) over fixed-shape caches; temperature 0 = greedy,
        else sampling (optionally top-k truncated)."""
        c = self.config
        self.eval()
        params = self.state_dict()
        ids = jnp.asarray(input_ids, jnp.int32)
        b, prompt_len = ids.shape
        if max_new_tokens <= 0:
            return ids
        total = prompt_len + max_new_tokens
        enforce(total <= c.max_position_embeddings,
                f"{total} positions exceed max_position_embeddings "
                f"({c.max_position_embeddings})")
        if key is None:
            key = fw_random.next_key()
        step = self._gen_step(float(temperature), int(top_k))

        caches = self.make_caches(b, total)
        out = [ids]
        key, sub = jax.random.split(key)
        nxt, caches = step(params, ids, caches,
                           jnp.asarray(0, jnp.int32), sub)
        out.append(nxt[:, None])
        finished = np.asarray(nxt == eos_token_id) \
            if eos_token_id is not None else None
        for i in range(1, max_new_tokens):
            key, sub = jax.random.split(key)
            # traced position: a python int would retrace every step
            nxt, caches = step(params, nxt[:, None], caches,
                               jnp.asarray(prompt_len + i - 1, jnp.int32),
                               sub)
            if eos_token_id is not None:
                # finished rows stay pinned to EOS (reference generate pads
                # completed sequences instead of sampling garbage)
                nxt = jnp.where(jnp.asarray(finished), eos_token_id, nxt)
                finished = finished | np.asarray(nxt == eos_token_id)
            out.append(nxt[:, None])
            if eos_token_id is not None and bool(np.all(finished)):
                break
        return jnp.concatenate(out, axis=1)

    def _gen_step(self, temperature: float, top_k: int):
        """One jitted decode step, cached per (temperature, top_k) on the
        instance so repeated generate() calls never recompile for the same
        shapes."""
        cache = getattr(self, "_gen_step_cache", None)
        if cache is None:
            cache = self._gen_step_cache = {}
        fn = cache.get((temperature, top_k))
        if fn is not None:
            return fn

        def step_fn(p, chunk, caches, pos, k):
            logits, new_caches = self.apply(p, chunk, caches, pos,
                                            method="generate_step")
            logits = logits[:, -1].astype(jnp.float32)     # (b, vocab)
            if temperature <= 0.0:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                scaled = logits / temperature
                if top_k > 0:
                    kth = lax.top_k(scaled, top_k)[0][:, -1][:, None]
                    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
                nxt = jax.random.categorical(k, scaled, axis=-1)
            return nxt.astype(jnp.int32), new_caches

        fn = jax.jit(step_fn)
        cache[(temperature, top_k)] = fn
        return fn


# -- standard configs (GPT-3 table; BASELINE.json configs) ------------------
# kwargs override the size defaults (e.g. gpt_tiny(num_layers=4))
def _cfg(defaults: Dict[str, Any], kw: Dict[str, Any]) -> GPTConfig:
    return GPTConfig(**{**defaults, **kw})


def gpt_tiny(**kw) -> GPTConfig:
    return _cfg(dict(hidden_size=128, num_layers=2, num_heads=4,
                     max_position_embeddings=256, vocab_size=1024), kw)


def gpt_125m(**kw) -> GPTConfig:
    return _cfg(dict(hidden_size=768, num_layers=12, num_heads=12), kw)


def gpt_350m(**kw) -> GPTConfig:
    return _cfg(dict(hidden_size=1024, num_layers=24, num_heads=16), kw)


def gpt_1p3b(**kw) -> GPTConfig:
    return _cfg(dict(hidden_size=2048, num_layers=24, num_heads=16,
                     max_position_embeddings=2048), kw)


def gpt_6p7b(**kw) -> GPTConfig:
    return _cfg(dict(hidden_size=4096, num_layers=32, num_heads=32,
                     max_position_embeddings=2048), kw)
