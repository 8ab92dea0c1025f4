"""A dropless expert layer that is told which experts it holds (ISSUE 28).

``distributed/moe.py`` holds the GShard layer: a ``tokens x experts x
capacity`` one-hot dispatch, top-1/top-2, tokens over capacity dropped,
its experts spread over a mesh axis.  This one is the serving-side layer
of a DeepSeekMoE block and lives in ``nn/`` because it holds no
collective: it is given ``ep_degree`` and ``ep_rank``, holds experts
``[held * ep_rank, held * (ep_rank + 1))`` of ``num_experts``, routes over
ALL of them, computes its own experts' part of the result for the tokens
routed to them and adds the shared experts.  Two published routers
(``scoring_func``), both scored in float32: ``"softmax"`` (DeepSeek-V2:
group-limited top-k, weights renormalised if asked and otherwise times
``routed_scaling_factor``) and ``"sigmoid"`` (``noaux_tc`` with one group,
DeepSeek-V3 and GLM-5: the top-k of ``sigmoid + router_bias`` chooses, the
unbiased sigmoid weighs, renormalised if asked AND times
``routed_scaling_factor``).  What absent experts would add is left out: on a mesh the
exchange goes around this layer, and on one chip nothing stands in for it.

An expert is a SwiGLU (``activation="swiglu"``) or, with no gate, ``relu(x
W_up)^2 W_down`` (``"relu2"``).  With ``latent_size`` the routed
experts work in a narrower latent: ``u = h W_latent_down`` goes in, their
weighted sum comes out through ``W_latent_up``; the router and the shared
expert read ``h`` at full width.  ``shared_width`` gives the shared expert
a width of its own (else ``n_shared_experts x expert_width``), in the
experts' activation.

Dropless: every (token, held expert) pair the router chose is computed.
The pairs are sorted by expert and the experts' products run over the
sorted rows (``ops/grouped_matmul.py``: Pallas kernels on a TPU,
``jax.lax.ragged_dot`` elsewhere), never as a one-hot over a capacity.

``forward`` returns ``(y, aux)``: ``aux["load"]`` the pairs each held
expert computed (``(held,)`` int32), ``aux["dropped"]`` the pairs that
found no row (0 by construction: the row buffer holds every pair), and
``aux["topk"]`` the router's choice (``(tokens, top_k)`` expert ids).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from ..ops.grouped_matmul import (grouped_matmul, grouped_relu2,
                                  grouped_swiglu)
from . import initializer as I
from .layer import Layer

__all__ = ["SwiGLU", "Relu2MLP", "DroplessMoE", "group_limited_topk"]


class SwiGLU(Layer):
    """Bias-free ``W_down(silu(x W_gate) * (x W_up))``."""

    def __init__(self, hidden_size: int, width: int, dtype="float32",
                 std: float = 0.02):
        super().__init__()
        init = I.NormalInDtype(std)
        self.w_gate = self.create_parameter((hidden_size, width), dtype, init)
        self.w_up = self.create_parameter((hidden_size, width), dtype, init)
        self.w_down = self.create_parameter((width, hidden_size), dtype, init)

    def forward(self, x):
        g = x @ self.w_gate.value
        return (jax.nn.silu(g) * (x @ self.w_up.value)) @ self.w_down.value


class Relu2MLP(Layer):
    """Bias-free ``W_down(relu(x W_up)^2)``."""

    def __init__(self, hidden_size: int, width: int, dtype="float32",
                 std: float = 0.02):
        super().__init__()
        init = I.NormalInDtype(std)
        self.w_up = self.create_parameter((hidden_size, width), dtype, init)
        self.w_down = self.create_parameter((width, hidden_size), dtype, init)

    def forward(self, x):
        u = jnp.maximum(x @ self.w_up.value, 0)
        return (u * u) @ self.w_down.value


def group_limited_topk(scores, n_group: int, topk_group: int, top_k: int):
    """DeepSeek-V2's ``group_limited_greedy``: a group's score is its best
    expert's; only the ``topk_group`` best groups stay; the ``top_k`` best
    experts among them.  ``scores (tokens, experts)`` -> ``(weights,
    expert ids)``, both ``(tokens, top_k)``."""
    t, e = scores.shape
    group = jnp.max(scores.reshape(t, n_group, e // n_group), axis=-1)
    _, kept = lax.top_k(group, topk_group)
    keep = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(keep, e // n_group, axis=1), scores, 0.0)
    return lax.top_k(masked, top_k)


class DroplessMoE(Layer):
    def __init__(self, hidden_size: int, expert_width: int,
                 num_experts: int, top_k: int, n_group: int = 1,
                 topk_group: int = 1, n_shared_experts: int = 0,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = False, ep_degree: int = 1,
                 ep_rank: int = 0, dtype="float32", std: float = 0.02,
                 scoring_func: str = "softmax", activation: str = "swiglu",
                 latent_size: Optional[int] = None,
                 shared_width: Optional[int] = None):
        super().__init__()
        enforce(scoring_func in ("softmax", "sigmoid"),
                f"scoring_func {scoring_func!r}")
        enforce(activation in ("swiglu", "relu2"),
                f"activation {activation!r}")
        enforce(scoring_func == "softmax" or n_group == 1,
                "the sigmoid router is built for one group")
        enforce(num_experts % ep_degree == 0 and 0 <= ep_rank < ep_degree,
                f"{num_experts} experts over ep_degree {ep_degree}, "
                f"rank {ep_rank}")
        enforce(num_experts % n_group == 0 and topk_group <= n_group,
                f"{num_experts} experts in {n_group} groups, "
                f"{topk_group} kept")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.ep_degree, self.ep_rank = int(ep_degree), int(ep_rank)
        self.held = self.num_experts // self.ep_degree
        self.scoring_func, self.activation = scoring_func, activation
        init = I.NormalInDtype(std)
        self.router = self.create_parameter(
            (hidden_size, num_experts), dtype, init)
        if scoring_func == "sigmoid":
            # the published correction bias (float32; trained by the
            # balancing rule, here drawn N(0, 0.02): enough to change
            # some choices of a sigmoid in (0, 1), whatever `std` is)
            self.router_bias = self.create_parameter(
                (num_experts,), "float32", I.NormalInDtype(0.02))
        self.latent_down = self.latent_up = None
        if latent_size:
            self.latent_down = self.create_parameter(
                (hidden_size, latent_size), dtype, init)
            self.latent_up = self.create_parameter(
                (latent_size, hidden_size), dtype, init)
        inner = latent_size or hidden_size
        if activation == "swiglu":
            self.w_gate = self.create_parameter(
                (self.held, inner, expert_width), dtype, init)
        self.w_up = self.create_parameter(
            (self.held, inner, expert_width), dtype, init)
        self.w_down = self.create_parameter(
            (self.held, expert_width, inner), dtype, init)
        mlp = SwiGLU if activation == "swiglu" else Relu2MLP
        self.shared = (mlp(hidden_size, shared_width
                           or n_shared_experts * expert_width, dtype, std)
                       if n_shared_experts else None)

    # -- routing -----------------------------------------------------------
    def route(self, h):
        """``(weights (tokens, top_k) float32, expert ids)`` over all
        ``num_experts``: the scores are computed in float32, as the
        published gate does."""
        logits = jnp.dot(h.astype(jnp.float32),
                         self.router.value.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if self.scoring_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = lax.top_k(scores + self.router_bias.value, self.top_k)
            w = jnp.take_along_axis(scores, idx, axis=-1)
            if self.norm_topk_prob:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            return w * self.routed_scaling_factor, idx
        scores = jax.nn.softmax(logits, axis=-1)
        w, idx = group_limited_topk(scores, self.n_group, self.topk_group,
                                    self.top_k)
        if self.top_k > 1 and self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        else:
            w = w * self.routed_scaling_factor
        return w, idx

    # -- the held experts' part ----------------------------------------------
    def experts(self, h, w, idx, valid=None, tile: Optional[int] = None):
        """``sum_e w_e expert_e(h)`` over the held experts among ``idx``;
        ``valid (tokens,)`` leaves padding tokens out.  ``tile`` is the
        row tile of the grouped product: 32 on a TPU (a tile of 128 was
        never faster there, at 256 tokens a step or at 1,024), and 1
        elsewhere (``ragged_dot`` over compact rows)."""
        t, hidden = h.shape
        k, held = self.top_k, self.held
        pairs = t * k
        if tile is None:
            tile = 32 if jax.default_backend() == "tpu" else 1
        rows = pairs if tile == 1 else \
            -(-(pairs + held * (tile - 1)) // tile) * tile
        local = idx.reshape(-1) - self.ep_rank * held
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine &= jnp.repeat(valid, k)
        key = jnp.where(mine, local, held)            # held = "not here"
        order = jnp.argsort(key, stable=True)
        skey = key[order]
        load = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
        padded = -(-load // tile) * tile
        start_c = jnp.cumsum(load) - load             # in the sorted order
        end_p = jnp.cumsum(padded[:held])
        start_p = jnp.concatenate([end_p - padded[:held],
                                   jnp.full((1,), rows, jnp.int32)])
        dest_sorted = jnp.where(
            skey < held,
            start_p[skey] + jnp.arange(pairs, dtype=jnp.int32)
            - start_c[skey], rows)
        # which pair sits in each row; `pairs` marks an empty (padding) row
        row_pair = jnp.full((rows,), pairs, jnp.int32).at[dest_sorted].set(
            order.astype(jnp.int32), mode="drop")
        dest = jnp.zeros((pairs,), jnp.int32).at[order].set(dest_sorted)
        placed = mine & (dest < rows)
        tiles = rows // tile
        tile_expert = jnp.minimum(jnp.searchsorted(
            end_p, jnp.arange(tiles, dtype=jnp.int32) * tile, side="right"),
            held - 1)
        tiles_used = end_p[-1] // tile
        h_ext = jnp.concatenate([h, jnp.zeros((1, hidden), h.dtype)])
        xs = jnp.take(h_ext, jnp.where(row_pair < pairs, row_pair // k, t),
                      axis=0)
        sizes = load[:held]
        if self.activation == "swiglu":
            act = grouped_swiglu(xs, self.w_gate.value, self.w_up.value,
                                 sizes, tile_expert, tiles_used, tile)
        else:
            act = grouped_relu2(xs, self.w_up.value, sizes, tile_expert,
                                tiles_used, tile)
        out = grouped_matmul(act, self.w_down.value, sizes, tile_expert,
                             tiles_used, tile)
        # rows nobody placed hold whatever was there: select, never scale
        got = jnp.where(placed[:, None],
                        jnp.take(out, jnp.minimum(dest, rows - 1), axis=0),
                        0).reshape(t, k, hidden)
        y = jnp.einsum("tk,tkh->th", w, got.astype(jnp.float32))
        aux = {"load": sizes,
               "dropped": jnp.sum(mine) - jnp.sum(placed),
               "topk": idx.astype(jnp.int32)}
        return y.astype(h.dtype), aux

    def forward(self, h, valid=None, tile: Optional[int] = None):
        """``h (tokens, hidden)`` -> ``(y, aux)``."""
        with jax.named_scope("moe.route"):
            w, idx = self.route(h)
        u = h
        if self.latent_down is not None:
            with jax.named_scope("moe.latent"):
                u = h @ self.latent_down.value
        with jax.named_scope("moe.experts"):
            y, aux = self.experts(u, w, idx, valid, tile)
        if self.latent_up is not None:
            with jax.named_scope("moe.latent"):
                y = y @ self.latent_up.value
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                y = y + self.shared(h)
        return y, aux
