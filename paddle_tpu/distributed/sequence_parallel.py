"""Sequence/context parallelism for long sequences.

The reference has NO sequence-parallel machinery (verified in SURVEY §5:
no ring attention / context parallel / Ulysses anywhere in the snapshot) —
its long-sequence levers are recompute and micro-batching.  This module is
the additive TPU-native capability the north star calls for, designed as
two composable pieces:

1. **Ulysses-style all-to-all SP** (`ulysses_qkv_spec` /
   `ulysses_out_spec` + the ``sequence_parallel`` flag on GPTConfig):
   activations are sequence-sharded over the ``sp`` mesh axis everywhere
   EXCEPT inside attention, where a layout change to head-sharding (heads
   over mp×sp, full sequence per shard) lets every device run its heads on
   the whole sequence.  Under GSPMD the layout change IS the pair of
   all-to-alls — expressed as two sharding constraints, XLA inserts and
   schedules the collectives over ICI.

2. **Ring attention** (`ring_attention`): true context parallelism where no
   device ever holds the full sequence.  Called inside ``shard_map`` with
   seq-sharded q/k/v; KV chunks rotate around the ``sp`` ring via
   ``ppermute`` while each rank maintains the online-softmax running
   (max, denominator, accumulator) over arriving chunks — the blockwise/
   ring-attention recurrence, with the flash kernel's math at chunk
   granularity and jnp ops so the backward differentiates through the
   ring (remat per chunk bounds memory).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from .collective import bound_axis_size

__all__ = ["ring_attention", "ring_attention_sharded", "shard_map"]

shard_map = jax.shard_map

_NEG_INF = -1e30


def _chunk_attn(q, k, v, row_off, col_off, *, scale, causal):
    """One (s_q, s_k) chunk's contribution: returns (m, l, acc) partials.

    q: (b, h, sq, d); k/v: (b, h, sk, d); offsets are the chunks' global
    sequence positions for causal masking."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = row_off + lax.broadcasted_iota(
            jnp.int32, s.shape, s.ndim - 2)
        cols = col_off + lax.broadcasted_iota(
            jnp.int32, s.shape, s.ndim - 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                       # (b, h, sq)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where((m <= _NEG_INF / 2)[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m_safe, l, acc


def ring_attention(q, k, v, axis_name: str, *, causal: bool = True,
                   scale: Optional[float] = None):
    """Context-parallel attention over a seq-sharded ring — call INSIDE
    shard_map with q, k, v of per-shard shape (b, h, s_local, d).

    Rank r owns query rows [r·s_local, (r+1)·s_local); KV chunks travel the
    ring so after n-1 rotations every rank has attended to the full
    sequence, holding only one chunk at a time (O(s_local) memory — the
    long-context property).  Communication is ``ppermute`` over ICI,
    overlappable with the chunk compute by XLA's scheduler.
    """
    n = bound_axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    if scale is None:
        scale = d ** -0.5
    row_off = idx * s_local
    chunk = jax.checkpoint(
        functools.partial(_chunk_attn, scale=scale, causal=causal))

    def step(i, carry):
        m, l, acc, kc, vc = carry
        src = jnp.mod(idx - i, n)                 # whose chunk we hold now
        cm, cl, cacc = chunk(q, kc, vc, row_off, src * s_local)
        m_new = jnp.maximum(m, cm)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(cm - m_new)
        l_new = alpha * l + beta * cl
        acc_new = (acc * alpha[..., None]
                   + cacc * beta[..., None].astype(cacc.dtype))
        perm = [(j, (j + 1) % n) for j in range(n)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return m_new, l_new, acc_new, kc, vc

    m0 = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    carry = (m0, l0, acc0, k, v)
    # python loop, not fori_loop: n is small (the sp degree) and unrolling
    # lets XLA overlap each ppermute with the next chunk's compute
    for i in range(n):
        carry = step(i, carry)
    m, l, acc, _, _ = carry
    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh=None, *, sp_axis: str = "sp",
                           dp_axis: str = "dp", mp_axis: str = "mp",
                           causal: bool = True,
                           scale: Optional[float] = None):
    """shard_map wrapper: q, k, v are GLOBAL (b, h, s, d) arrays living on
    the active hybrid mesh; sequence sharded over ``sp``, batch over
    ``dp``, heads over ``mp`` (any of which may be absent)."""
    from jax.sharding import PartitionSpec as P
    from .mp_layers import _clean_spec
    from .topology import get_mesh
    mesh = mesh or get_mesh()
    enforce(mesh is not None and sp_axis in mesh.axis_names,
            f"ring_attention_sharded needs a mesh with axis {sp_axis!r}")
    spec = _clean_spec(mesh, (dp_axis, mp_axis, sp_axis, None))
    fn = functools.partial(ring_attention, axis_name=sp_axis,
                           causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
