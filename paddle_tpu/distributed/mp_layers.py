"""Tensor-(model-)parallel layers.

Reference: fleet/meta_parallel/mp_layers.py — ``VocabParallelEmbedding``:30,
``ColumnParallelLinear``:97, ``RowParallelLinear``:170 (Megatron-style
splits), with collective ops `c_embedding` / `_mp_allreduce` / `c_split`
(collective.py:1167,1128; c_embedding_op.cc).

TPU-native design — the crucial departure from the reference: parameters stay
**global-shaped**; the split lives in a ``PartitionSpec`` attached to each
parameter (``Parameter.pspec``) and in sharding constraints on activations.
GSPMD then partitions the matmuls over the ``mp`` mesh axis and inserts
exactly the collectives the reference codes by hand:

- ColumnParallelLinear: W (in, out) sharded P(None,'mp') → output sharded on
  features; ``gather_output=True`` constrains the output replicated, which
  lowers to the all-gather the reference does with c_concat.
- RowParallelLinear: W sharded P('mp',None), input sharded on features → the
  contraction produces partial sums and GSPMD inserts the psum that the
  reference's `_mp_allreduce` performs.
- VocabParallelEmbedding: table sharded over vocab rows; the gather over a
  sharded axis lowers to the mask-lookup+psum of c_embedding_op.cc.

No weight is ever materialized per-rank in python — one program, one logical
weight, XLA owns the distribution.  Works unchanged when no mesh is active
(the specs are inert metadata), so serial and parallel runs share code —
the parallel==serial invariant (SURVEY §4) holds by construction.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.errors import enforce
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, Parameter
from .topology import get_mesh

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear",
           "RowParallelLinear", "shard_constraint", "param_sharding",
           "variables_sharding"]


def _clean_spec(mesh, spec) -> P:
    """Drop spec entries naming axes the mesh doesn't have (a TP spec on a
    pure-DP mesh degrades to replicated on that dim — serial-compatible)."""
    cleaned = tuple(s if (s is None or all(
        a in mesh.axis_names for a in ((s,) if isinstance(s, str) else s)))
        else None for s in spec)
    return P(*cleaned)


def shard_constraint(x, *spec, mesh=None):
    """with_sharding_constraint against the active hybrid mesh; no-op when no
    mesh is registered or the axes aren't in it (serial mode)."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _clean_spec(mesh, spec)))


def param_sharding(p, mesh=None) -> Optional[NamedSharding]:
    """NamedSharding for one Parameter from its pspec (replicated default)."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return None
    spec = getattr(p, "pspec", None) or P()
    return NamedSharding(mesh, _clean_spec(mesh, spec))


def variables_sharding(layer: Layer, mesh=None):
    """{name: NamedSharding} for every parameter/buffer of ``layer`` — feed
    to jit in_shardings / jax.device_put to place the model on the mesh."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return None
    out = {}
    for name, p in layer.named_parameters():
        out[name] = param_sharding(p, mesh)
    for name, _ in layer.named_buffers():
        out[name] = NamedSharding(mesh, P())
    return out


class ColumnParallelLinear(Layer):
    """Y = X @ W[:, shard] (+b[shard]) — reference mp_layers.py:97.

    weight: (in_features, out_features) with pspec P(None, 'mp').
    gather_output=True replicates the output (c_concat analog); False keeps
    it feature-sharded for a following RowParallelLinear.
    """

    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, has_bias: bool = True,
                 gather_output: bool = True, mp_axis: str = "mp",
                 fuse_matmul_bias: bool = False, name: Optional[str] = None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.mp_axis = mp_axis
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr)
        self.weight.pspec = P(None, mp_axis)
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.pspec = P(mp_axis)
        else:
            self.bias = None

    def forward(self, x):
        with jax.named_scope("mp.column_parallel"):
            y = F.linear(x, self.weight, self.bias)
            if self.gather_output:
                return shard_constraint(y, *((None,) * y.ndim))
            return shard_constraint(y, *((None,) * (y.ndim - 1)),
                                    self.mp_axis)


class RowParallelLinear(Layer):
    """Y = sum_over_shards(X[shard] @ W[shard, :]) + b — reference
    mp_layers.py:170.  weight: (in_features, out_features), pspec
    P('mp', None); the contraction over the sharded axis makes GSPMD emit
    the `_mp_allreduce`."""

    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, has_bias: bool = True,
                 input_is_parallel: bool = False, mp_axis: str = "mp",
                 name: Optional[str] = None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.mp_axis = mp_axis
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr)
        self.weight.pspec = P(mp_axis, None)
        if has_bias:
            # bias added after the cross-shard sum → replicated
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.pspec = P()
        else:
            self.bias = None

    def forward(self, x):
        # the all-reduce GSPMD inserts to complete the contraction inherits
        # this scope
        with jax.named_scope("mp.row_parallel"):
            if self.input_is_parallel:
                x = shard_constraint(
                    x, *((None,) * (jnp.ndim(x) - 1)), self.mp_axis)
            y = F.linear(x, self.weight, None)
            y = shard_constraint(y, *((None,) * jnp.ndim(y)))
            if self.bias is not None:
                y = y + self.bias.value.astype(y.dtype)
            return y


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dimension sharded over mp — reference
    mp_layers.py:30 (c_embedding_op.cc: local lookup with start_index offset,
    OOV rows zero, summed by mp_allreduce; GSPMD derives the same plan from
    the row-sharded gather)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_attr=None, mp_axis: str = "mp",
                 name: Optional[str] = None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.mp_axis = mp_axis
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=None if getattr(
                weight_attr, "initializer", None) else I.Normal(std=0.02))
        self.weight.pspec = P(mp_axis, None)

    def forward(self, ids):
        out = F.embedding(ids, self.weight)
        return shard_constraint(out, *((None,) * jnp.ndim(out)))
