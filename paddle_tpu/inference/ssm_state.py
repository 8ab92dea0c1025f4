"""A Mamba-2 layer's state a sequence, and the two forms of its scan.

Per head ``n`` of ``heads``, reading group ``g = n // (heads / groups)`` of
the ``groups`` input and output projections ``B`` and ``C``, with ``dt``
already through its softplus:

    S_t = exp(dt_t A_n) S_{t-1} + dt_t x_{t,n} (outer) B_{t,g}
    y_{t,n} = S_t C_{t,g} + D_n x_{t,n}

``S`` is ``(head_dim, state)`` a head, float32.  A sequence keeps it in its
slot of a state layer's pool (``kv_cache.StateLayer``), beside the last
``conv_kernel`` inputs of the layer's causal convolution (its WINDOW: the
family's cache keeps the same; a DMA of a slot's rows needs a whole tile of
them, and ``conv_kernel - 1`` = 3 is not one).

- :func:`ssm_state_decode_pallas` — the decode kernel ``ssm_state_decode``:
  one token a row, the slot's state read from HBM, stepped once and
  written back IN PLACE (``input_output_aliases``; the step program is
  handed the pool donated), and the row's new convolution tail written into
  its slot beside it.  The grid walks rows outermost and blocks of ``G``
  groups innermost (a group's heads are those that read one ``B`` and
  ``C``); the slot a row is a scalar-prefetch operand.  ``G`` is
  :func:`_groups_a_step`'s: the largest divisor of the groups whose state
  blocks in and out, double-buffered, the row's other blocks and a group's
  float32 temporaries fit under the kernel's VMEM limit (all 8 groups, a
  row's whole 4 MiB state, at Nemotron-3-Super's widths: 128 steps a call
  for 128 rows).  A step walks its groups one at a time; ``y = S C`` is a
  product on the MXU (``C`` against the heads' rows of ``S``: ``y``
  lane-dense, no reduction across lanes).  The window's ``G`` groups of
  channels go to the row's slot by an ASYNCHRONOUS DMA, started before the
  state is stepped and waited at the step's end: a synchronous one queued
  behind the state's own DMAs and waited for them.  The tails stay in HBM;
  :func:`ssm_tail_read`, ``ssm_tail_read`` in a trace, gathers the rows'
  old tails from there.  (With an XLA gather and scatter of 128 tails the
  compiler kept every tail array in VMEM for the step: a copy in and a
  copy out a layer.)  Padding rows (``seq_lens`` 0) compute and write
  nothing; their slot is the pool's spare, the one no sequence holds, and
  they stay on its last block, so they are consecutive blocks of one index
  and the pipeline moves it once.
- :func:`ssm_state_decode_reference` — the same in ``jax.numpy``: the
  route off the TPU and the kernel's oracle.
- :func:`ssm_chunk_scan` — a chunk of tokens from a given state, in XLA:
  the chunked (SSD) form, ``chunk`` tokens at a time, the products inside a
  chunk as masked matrices and the state carried from one chunk to the
  next.  Padded positions carry ``dt = 0``: the state does not move there,
  so the final state is the one at the last real token.
- :func:`causal_conv` — the depthwise causal convolution over a chunk from
  a given window, and the window after the last real input;
  :func:`conv_step` the same for one token a row, on ``(rows, channels)``
  arrays (a chunk of one as ``(rows, 1, channels)`` has the compiler lay
  every such array out a row a tile).
- :func:`initial_state` — what a row starts from: its slot's state, or
  zeros where the row starts its sequence (position 0).  That is the reset
  of a slot that a new sequence has taken.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..ops.flash_attention import _interpret

__all__ = ["ssm_state_decode", "ssm_state_decode_pallas",
           "ssm_state_decode_reference", "ssm_tail_read", "ssm_chunk_scan",
           "causal_conv", "conv_step", "initial_state"]

_VMEM_LIMIT = 32 << 20
# float32 arrays of a group's state size that a grid step of the decode
# kernel holds beside its blocks: the decayed state, the update, the sum
_GROUP_TEMPS = 3
_HI = lax.Precision.HIGHEST


def initial_state(tails, states, slots, fresh):
    """``(conv windows (b, k, channels), states (b, heads, head_dim,
    state))`` a row starts from: its slot's, or zeros where ``fresh`` (the
    row's first position is 0: a sequence that has just taken the slot)."""
    t = jnp.take(tails, slots, axis=0, mode="clip")
    s = jnp.take(states, slots, axis=0, mode="clip")
    return (jnp.where(fresh[:, None, None], 0, t).astype(tails.dtype),
            jnp.where(fresh[:, None, None, None], 0, s).astype(states.dtype))


def causal_conv(xbc, window, weight, bias, lens):
    """The depthwise causal convolution of ``xbc (b, s, channels)`` after
    the inputs ``window (b, k, channels)`` (the last ``k`` before it, the
    newest last), ``weight (k, channels)``, ``bias (channels,)``, through
    SiLU, in float32; and the window after the last of the row's ``lens``
    real inputs (``lens`` >= 1; the old window's rows where the row has
    fewer than ``k``)."""
    k = weight.shape[0]
    s = xbc.shape[1]
    xp = jnp.concatenate([window[:, 1:].astype(jnp.float32),
                          xbc.astype(jnp.float32)], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(xp[:, i:i + s] * w[i] for i in range(k))
    out = jax.nn.silu(out + bias.astype(jnp.float32))
    new = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
        row, n - 1, k, axis=0))(xp, jnp.asarray(lens, jnp.int32))
    return out, new.astype(window.dtype)


def conv_step(xbc, window, weight, bias):
    """One token a row: ``xbc (rows, channels)`` after ``window (rows, k,
    channels)`` -> ``(SiLU(conv + bias) (rows, channels) float32, the new
    window: the last ``k - 1`` rows of the old and ``xbc``)``."""
    k = weight.shape[0]
    w = weight.astype(jnp.float32)
    out = xbc.astype(jnp.float32) * w[k - 1] + bias.astype(jnp.float32)
    for i in range(k - 1):
        out = out + window[:, i + 1].astype(jnp.float32) * w[i]
    new = jnp.concatenate([window[:, 1:], xbc[:, None].astype(window.dtype)],
                          axis=1)
    return jax.nn.silu(out), new


def _segment_scan(carry, xs, a_log_free, hpg):
    """One chunk: ``xs = (x (b, L, h, p), dt (b, L, h), B, C (b, L, g,
    n))`` from state ``carry (b, h, p, n)``."""
    x, dt, bm, cm = xs
    b, L, h, p = x.shape
    g = bm.shape[2]
    n = bm.shape[3]
    a = dt * a_log_free                               # (b, L, h)
    cum = jnp.cumsum(a, axis=1)
    seg = cum[:, :, None, :] - cum[:, None, :, :]     # (b, l, s, h)
    causal = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("blgn,bsgn->blsg", cm, bm, precision=_HI)
    w = (cb[..., None] * decay.reshape(b, L, L, g, hpg)
         * dt.reshape(b, 1, L, g, hpg))
    xg = x.reshape(b, L, g, hpg, p)
    y = jnp.einsum("blsgk,bsgkp->blgkp", w, xg, precision=_HI)
    sg = carry.reshape(b, g, hpg, p, n)
    y = y + (jnp.einsum("blgn,bgkpn->blgkp", cm, sg, precision=_HI)
             * jnp.exp(cum).reshape(b, L, g, hpg)[..., None])
    to_end = (jnp.exp(cum[:, -1:, :] - cum) * dt).reshape(b, L, g, hpg)
    new = (carry * jnp.exp(cum[:, -1])[:, :, None, None]
           + jnp.einsum("bsgk,bsgkp,bsgn->bgkpn", to_end, xg, bm,
                        precision=_HI).reshape(b, h, p, n))
    return new, y.reshape(b, L, h, p)


def ssm_chunk_scan(x, dt, a, bm, cm, d, state, chunk: int):
    """``x (b, s, heads, head_dim)``, ``dt (b, s, heads)`` (after the
    softplus; 0 at padded positions), ``a (heads,)`` (``-exp(A_log)``),
    ``bm`` / ``cm (b, s, groups, state)``, ``d (heads,)``, ``state (b,
    heads, head_dim, state)`` to start from -> ``(y (b, s, heads,
    head_dim), final state)``, float32, ``chunk`` tokens at a time."""
    b, s, h, p = x.shape
    g = bm.shape[2]
    f32 = lambda t: t.astype(jnp.float32)          # noqa: E731
    x, dt, bm, cm = f32(x), f32(dt), f32(bm), f32(cm)
    L = min(chunk, s)
    pad = -s % L
    if pad:
        grow = lambda t: jnp.pad(                  # noqa: E731
            t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, bm, cm = grow(x), grow(dt), grow(bm), grow(cm)
    c = (s + pad) // L
    cut = lambda t: jnp.moveaxis(                  # noqa: E731
        t.reshape((b, c, L) + t.shape[2:]), 1, 0)
    with jax.named_scope("ssm.chunk_scan"):
        final, ys = lax.scan(
            functools.partial(_segment_scan, a_log_free=f32(a), hpg=h // g),
            f32(state), (cut(x), cut(dt), cut(bm), cut(cm)))
        y = jnp.moveaxis(ys, 0, 1).reshape(b, c * L, h, p)[:, :s]
    return y + f32(d)[:, None] * x[:, :s], final


# ---------------------------------------------------------------------------
# Decode: one token a row, the state stepped in place
# ---------------------------------------------------------------------------
def ssm_state_decode_reference(states, tails, new_tails, slots, lens, x, dt,
                               a, bm, cm, d):
    """``states (slots + 1, heads, head_dim, state)`` float32, ``tails
    (slots + 1, k, channels)`` (the convolution's windows) and the rows'
    ``new_tails (rows, k, channels)``, ``slots`` / ``lens (rows,)``, ``x (rows, heads,
    head_dim)``, ``dt (rows, heads)`` (after the softplus), ``a`` / ``d
    (heads,)``, ``bm`` / ``cm (rows, groups, state)`` -> ``(y (rows, heads,
    head_dim) float32, states, tails)``: each live row's slot stepped once
    and given its new tail; rows with ``lens`` 0 write nothing."""
    rows, h, p = x.shape
    hpg = h // bm.shape[1]
    f32 = lambda t: t.astype(jnp.float32)          # noqa: E731
    x, dt = f32(x), f32(dt)
    bh = jnp.repeat(f32(bm), hpg, axis=1)          # (rows, h, n)
    ch = jnp.repeat(f32(cm), hpg, axis=1)
    s = jnp.take(states, slots, axis=0, mode="clip")
    s = (s * jnp.exp(dt * f32(a))[:, :, None, None]
         + (dt[:, :, None] * x)[..., None] * bh[:, :, None, :])
    y = jnp.einsum("rhpn,rhn->rhp", s, ch, precision=_HI) \
        + f32(d)[None, :, None] * x
    at = jnp.where(lens > 0, slots, states.shape[0])
    return (y, states.at[at].set(s, mode="drop"),
            tails.at[at].set(new_tails.astype(tails.dtype), mode="drop"))


def _tail_kernel(slots_ref, tails_ref, o_ref):
    from jax.experimental.pallas import tpu as pltpu
    pltpu.sync_copy(tails_ref.at[pl.ds(slots_ref[pl.program_id(0)], 1)],
                    o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tail_read_pallas(tails, slots, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    rows = slots.shape[0]
    k, channels = tails.shape[1:]
    return pl.pallas_call(
        _tail_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((1, k, channels),
                                   lambda i, sl: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, k, channels), tails.dtype),
        name="ssm_tail_read", interpret=interpret,
    )(jnp.asarray(slots, jnp.int32), tails)


def ssm_tail_read(tails, slots):
    """``tails[slots]`` (``(rows, k, channels)``): a DMA a row from the
    tails in HBM on a TPU, ``jnp.take`` elsewhere."""
    if jax.default_backend() != "tpu":
        return jnp.take(tails, slots, axis=0, mode="clip")
    return _tail_read_pallas(tails, slots, interpret=_interpret())


def _groups_a_step(groups, state_bytes, other_bytes, vmem=_VMEM_LIMIT):
    """The groups a grid step of ``ssm_state_decode`` covers: the largest
    divisor ``G`` of ``groups`` whose blocks, each double-buffered (the state
    in and out, 4 x ``G`` x a group's ``state_bytes``; the row's other
    blocks, 4 x ``G`` x ``other_bytes``), with a group's float32
    temporaries beside them, fit under ``vmem``; 1 where none does."""
    def fits(g):
        return (4 * g * (state_bytes + other_bytes)
                + _GROUP_TEMPS * state_bytes <= vmem)
    return max((g for g in range(1, groups + 1)
                if groups % g == 0 and fits(g)), default=1)


def _vmem_bytes(shape, dtype):
    """A block's bytes in VMEM: its last two dims padded to whole tiles,
    ``(8, 128)`` in float32, ``(16, 128)`` in bf16."""
    item = jnp.dtype(dtype).itemsize
    sub = 32 // item
    *lead, r, c = shape
    return (math.prod(lead) * -(-r // sub) * sub * -(-c // 128) * 128
            * item)


def _group_bytes(hb, p, n, k, cw, tail_dtype):
    """``(state, other)``: one group's VMEM bytes in a grid step of
    ``ssm_state_decode``: its heads' state block, and its share of the
    row's other blocks (x in both layouts, y, dt, A, D, B, C and ``cw``
    channels of the ``k``-row window)."""
    f32 = jnp.float32
    other = (_vmem_bytes((hb, p), f32) + 3 * _vmem_bytes((1, hb * p), f32)
             + 2 * _vmem_bytes((hb, 1), f32) + 2 * _vmem_bytes((1, n), f32)
             + _vmem_bytes((k, cw), tail_dtype))
    return _vmem_bytes((hb, p, n), f32), other


def _decode_kernel(slots_ref, lens_ref, x_ref, xf_ref, dt_ref, a_ref, b_ref,
                   c_ref, d_ref, new_tail_ref, s_ref, t_ref, y_ref, s_out_ref,
                   t_out_ref, sem):
    from jax.experimental.pallas import tpu as pltpu
    del t_ref                         # the same HBM buffer as t_out_ref
    i, j = pl.program_id(0), pl.program_id(1)
    gs, hb, p = x_ref.shape[1:]
    n = b_ref.shape[3]
    cw = new_tail_ref.shape[2]
    window = pltpu.make_async_copy(new_tail_ref, t_out_ref.at[
        pl.ds(slots_ref[i], 1), :, pl.ds(j * cw, cw)], sem)

    @pl.when(lens_ref[i] > 0)
    def _():
        window.start()                # lands while the state is stepped

        def group(g, carry):
            heads = pl.ds(g * hb, hb)
            dt = dt_ref[0, g]                           # (hb, 1)
            s = (s_ref[0, heads] * jnp.exp(dt * a_ref[g])[:, :, None]
                 + (dt * x_ref[0, g])[:, :, None] * b_ref[0, g][None])
            s_out_ref[0, heads] = s                     # (hb, p, n)
            # y = S C on the MXU: C on 8 rows against the heads' (hb x p)
            # rows of S gives y lane-dense, with no lane reduction
            sc = lax.dot_general(
                jnp.broadcast_to(c_ref[0, g], (8, n)), s.reshape(hb * p, n),
                (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)     # (8, hb * p)
            y_ref[0, g] = sc[:1] + d_ref[g] * xf_ref[0, g]
            return carry

        lax.fori_loop(0, gs, group, 0)
        window.wait()


@functools.partial(jax.jit, static_argnames=("gs", "interpret"))
def _decode_call(states, tails, new_tails, slots, lens, x, dt, a, bm, cm, d,
                 gs: int, interpret: bool):
    """The ``pallas_call``, ``gs`` groups a grid step (the program takes
    them from :func:`_groups_a_step`)."""
    from jax.experimental.pallas import tpu as pltpu
    rows, h, p = x.shape
    groups, n = bm.shape[1], bm.shape[2]
    hb = h // groups                                # a group's heads
    k, channels = tails.shape[1:]
    cw = channels // groups * gs                    # a grid step's channels
    blocks = groups // gs
    f32 = lambda t: t.astype(jnp.float32)          # noqa: E731

    def by_row(shape):
        return pl.BlockSpec((1, gs) + shape,
                            lambda i, j, sl, ln: (i, j) + (0,) * len(shape))

    def by_group(shape):
        return pl.BlockSpec((gs,) + shape,
                            lambda i, j, sl, ln: (j,) + (0,) * len(shape))

    # a padding row stays on its spare slot's last block: consecutive
    # steps of one block index, which the pipeline moves once
    state_spec = pl.BlockSpec(
        (1, gs * hb, p, n),
        lambda i, j, sl, ln: (sl[i], jnp.where(ln[i] > 0, j, blocks - 1),
                              0, 0))
    tail_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # slots, seq_lens
        grid=(rows, blocks),
        in_specs=[by_row((hb, p)), by_row((1, hb * p)), by_row((hb, 1)),
                  by_group((hb, 1)), by_row((1, n)), by_row((1, n)),
                  by_group((1, hb * p)),
                  pl.BlockSpec((1, k, cw), lambda i, j, sl, ln: (i, 0, j)),
                  state_spec, tail_spec],
        out_specs=[by_row((1, hb * p)), state_spec, tail_spec],
        scratch_shapes=[pltpu.SemaphoreType.DMA(())])
    y, states, tails = pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, groups, 1, hb * p),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        # the state and the tails, counted after the 2 prefetched
        input_output_aliases={10: 1, 11: 2},
        name="ssm_state_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(slots, jnp.int32), jnp.asarray(lens, jnp.int32),
      f32(x).reshape(rows, groups, hb, p),
      f32(x).reshape(rows, groups, 1, hb * p),
      f32(dt).reshape(rows, groups, hb, 1), f32(a).reshape(groups, hb, 1),
      f32(bm)[:, :, None, :], f32(cm)[:, :, None, :],
      jnp.repeat(f32(d), p).reshape(groups, 1, hb * p),
      new_tails.astype(tails.dtype), states, tails)
    return y.reshape(rows, h, p), states, tails


def ssm_state_decode_pallas(states, tails, new_tails, slots, lens, x, dt, a,
                            bm, cm, d, interpret: Optional[bool] = None):
    """The kernel (interpret-mode off TPU); arguments and results as
    :func:`ssm_state_decode_reference`'s, but ``states`` and ``tails`` are
    written in place and their spare slot (the padding rows') may be left
    anyhow.  A grid step covers the groups :func:`_groups_a_step` picks from
    the shapes; the call is jitted, so that a step program lowers it once
    for all its layers."""
    groups, n = bm.shape[1], bm.shape[2]
    hb, p = x.shape[1] // groups, x.shape[2]
    k, channels = tails.shape[1:]
    gs = _groups_a_step(groups, *_group_bytes(
        hb, p, n, k, channels // groups, tails.dtype))
    return _decode_call(states, tails, new_tails, slots, lens, x, dt, a, bm,
                        cm, d, gs=gs,
                        interpret=_interpret() if interpret is None
                        else interpret)


def ssm_state_decode(states, tails, new_tails, slots, lens, x, dt, a, bm, cm,
                     d):
    """The kernel on a TPU, the reference elsewhere (interpret-mode
    Pallas is orders slower than XLA on the CPU)."""
    impl = (ssm_state_decode_pallas if jax.default_backend() == "tpu"
            else ssm_state_decode_reference)
    return impl(states, tails, new_tails, slots, lens, x, dt, a, bm, cm, d)
