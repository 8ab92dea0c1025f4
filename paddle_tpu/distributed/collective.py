"""Collective communication API.

Reference: paddle/fluid/operators/collective/ (143 op files — c_allreduce_*,
c_allgather, c_reducescatter, alltoall, c_broadcast, partial_send/recv...) and
the eager ``ProcessGroup`` API (distributed/collective/ProcessGroup.h:53).

TPU-native design: every byte-level transport (NCCL rings, ring_id registry,
gen_comm_id bootstrap) collapses into XLA collectives over ICI/DCN.  A
"process group" is a mesh axis name; these functions lower to ``jax.lax``
collectives and are valid inside ``shard_map``/``pjit``-parallelized code.
Called outside any mesh axis they are identity (world size 1) — the same
behavior paddle has when dist is not initialized.

The reference's eager tensor-in-place mutation API is reshaped functional:
``y = dist.all_reduce(x, group='mp')`` returns the result.
"""
from __future__ import annotations

import functools
import inspect
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.errors import enforce
from .topology import axis_size

__all__ = [
    "ReduceOp", "all_reduce", "all_reduce_quantized", "all_gather",
    "reduce_scatter", "broadcast", "all_to_all", "reduce", "scatter",
    "send_recv_permute", "barrier", "split", "p2p_push",
]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _observed(fn):
    """Record per-collective host latency into the telemetry registry
    (ISSUE 3): histogram ``collective.<op>.ms`` + counter
    ``collective.<op>.calls``.  For ops invoked inside a traced program
    this measures trace/dispatch cost (the wire time lives in the XLA
    schedule); for host-blocking ops — ``barrier`` above all — it is the
    real wait, which is exactly the number a wedged fleet shows first.

    ISSUE 20: when the call's ``group`` is a mesh-axis name, the
    instruments carry ``[axis=<group>,n=<participants>]`` labels
    (name-suffix convention; parse with
    :func:`~paddle_tpu.observability.registry.split_labels`) plus a
    ``collective.<op>.bytes[...]`` payload counter, so the interconnect
    microscope can attribute wire time per (op, axis).  Label
    extraction is strictly best-effort — any failure falls back to the
    legacy unlabeled names rather than raising out of a collective."""
    base = f"collective.{fn.__name__}"
    try:
        params = list(inspect.signature(fn).parameters.values())
        group_idx = next(i for i, p in enumerate(params)
                         if p.name == "group")
        group_default = params[group_idx].default
        if group_default is inspect.Parameter.empty:
            group_default = None
    except (StopIteration, TypeError, ValueError):
        group_idx, group_default = None, None

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            dt_ms = (time.perf_counter() - t0) * 1e3
            suffix = ""
            nbytes = 0
            try:
                group = kwargs.get("group", group_default)
                if ("group" not in kwargs and group_idx is not None
                        and len(args) > group_idx):
                    group = args[group_idx]
                if isinstance(group, str):
                    n = (bound_axis_size(group) if _in_axis(group)
                         else axis_size(group))
                    suffix = f"[axis={group},n={int(n)}]"
                x = args[0] if args else None
                if (x is not None and hasattr(x, "size")
                        and hasattr(x, "dtype")):
                    nbytes = int(x.size) * int(x.dtype.itemsize)
            except Exception:  # noqa: BLE001 — labels never break a call
                suffix, nbytes = "", 0
            from ..observability import get_registry
            reg = get_registry()
            reg.histogram(f"{base}.ms{suffix}").observe(dt_ms)
            reg.counter(f"{base}.calls{suffix}").inc()
            if nbytes and suffix:
                reg.counter(f"{base}.bytes{suffix}").inc(nbytes)
    return wrapped


def bound_axis_size(name: str):
    """Size of a bound (shard_map/pmap) axis."""
    return lax.axis_size(name)


def _in_axis(group: Optional[str]) -> bool:
    """True when ``group`` names an axis bound in the current trace
    (inside shard_map over that axis)."""
    if group is None:
        return False
    try:
        bound_axis_size(group)
        return True
    except (NameError, KeyError, ValueError):
        return False


def _arr(x):
    return x.__jax_array__() if hasattr(x, "__jax_array__") else jnp.asarray(x)


@_observed
def all_reduce(x, op: str = ReduceOp.SUM, group: Optional[str] = "dp"):
    """c_allreduce_{sum,max,min,prod} (reference collective/c_allreduce_op.h).
    ``group`` is a mesh axis name or tuple of axis names."""
    x = _arr(x)
    if not _in_axis(group if isinstance(group, str) else (group or [None])[0]):
        return x
    if op == ReduceOp.SUM:
        return lax.psum(x, group)
    if op == ReduceOp.MAX:
        return lax.pmax(x, group)
    if op == ReduceOp.MIN:
        return lax.pmin(x, group)
    if op == ReduceOp.AVG:
        return lax.pmean(x, group)
    if op == ReduceOp.PROD:
        # gather-then-prod: exact for zeros/negatives/ints (an exp-of-
        # psum-of-logs trick would NaN on non-positive values)
        gathered = lax.all_gather(x, group, axis=0)
        return jnp.prod(gathered, axis=0)
    raise ValueError(f"unknown reduce op {op!r}")


@_observed
def all_gather(x, group: Optional[str] = "dp", axis: int = 0,
               tiled: bool = True):
    """c_allgather (reference collective/c_allgather_op.cc): concatenate the
    per-device shards along ``axis``."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    return lax.all_gather(x, group, axis=axis, tiled=tiled)


@_observed
def reduce_scatter(x, op: str = ReduceOp.SUM, group: Optional[str] = "dp",
                   axis: int = 0):
    """c_reducescatter (reference collective/c_reducescatter_op.cc)."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    return lax.psum_scatter(x, group, scatter_dimension=axis, tiled=True)


@_observed
def broadcast(x, src: int = 0, group: Optional[str] = "dp"):
    """c_broadcast: every device gets src's value.  Implemented as a
    masked psum (XLA lowers single-source psum patterns to a broadcast)."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    idx = lax.axis_index(group)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, group)


@_observed
def reduce(x, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[str] = "dp"):
    """c_reduce: full result lands on dst, zeros elsewhere (SPMD shape must
    be uniform; callers normally follow with work on dst only)."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    total = all_reduce(x, op, group)
    idx = lax.axis_index(group)
    return jnp.where(idx == dst, total, jnp.zeros_like(total))


@_observed
def scatter(x, src: int = 0, group: Optional[str] = "dp", axis: int = 0):
    """Each device keeps its slice of src's tensor."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    n = bound_axis_size(group)
    if x.shape[axis] % n:
        raise ValueError(
            f"scatter axis {axis} size {x.shape[axis]} not divisible by "
            f"group size {n}")
    x = broadcast(x, src, group)
    idx = lax.axis_index(group)
    size = x.shape[axis] // n
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis=axis)


@_observed
def all_to_all(x, group: Optional[str] = "ep", split_axis: int = 0,
               concat_axis: int = 0):
    """alltoall (reference collective/alltoall_op.cc; MoE dispatch backbone
    global_scatter_op.cc)."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    return lax.all_to_all(x, group, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


@_observed
def send_recv_permute(x, perm: Sequence[tuple], group: str = "pp"):
    """Point-to-point via collective_permute — the ICI-native replacement for
    the reference's NCCL send/recv pairs (partial_send/recv,
    pp_utils/p2p_communication.py).  ``perm`` is [(src, dst), ...]."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    return lax.ppermute(x, group, perm=list(perm))


@_observed
def p2p_push(x, offset: int = 1, group: str = "pp"):
    """Shift along a ring: stage i sends to stage i+offset (mod n) — the 1F1B
    forward/backward activation hand-off."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    n = bound_axis_size(group)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, group, perm=perm)


@_observed
def split(x, group: str = "mp", axis: int = -1):
    """c_split: keep this device's slice along ``axis``."""
    x = _arr(x)
    if not _in_axis(group):
        return x
    n = bound_axis_size(group)
    idx = lax.axis_index(group)
    ax = axis % x.ndim
    if x.shape[ax] % n:
        raise ValueError(
            f"split axis {ax} size {x.shape[ax]} not divisible by "
            f"group size {n}")
    size = x.shape[ax] // n
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis=ax)


@_observed
def barrier(group: Optional[str] = None, timeout: Optional[float] = None):
    """Host-side rendezvous.  Inside a traced program this is a no-op
    (one program, one schedule — XLA's execution model is the barrier;
    reference collective/barrier_op.cc is an allreduce on a scalar).
    Called from host code on a multi-process run it blocks until every
    process arrives — and that wait is exactly where a dead or wedged
    peer hangs the fleet, so it runs under the run supervisor's watchdog
    when one is installed: instead of blocking forever the caller gets a
    ``StepTimeout`` (plus an all-thread stack dump in the supervisor
    report).  ``timeout`` overrides the watchdog's default deadline for
    this wait only."""
    from ..supervisor.watchdog import guarded
    if _in_axis(group):
        return None  # traced: SPMD already orders the program
    with guarded("collective.barrier", timeout=timeout):
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("paddle_tpu.barrier")
    return None


def all_reduce_quantized(x, group: str = "dp", bits: int = 8,
                         block_size: int = 256):
    """DEPRECATED alias for the comm package's quantized all-reduce
    (ISSUE 8): ``comm.all_reduce(x, config=CommConfig(dtype="int8"))``.

    The historical stub here carried int16 payloads because a stock psum
    cannot sum int8 without cross-lane overflow; the comm package's
    two-phase schedule (quantize → all_to_all reduce-scatter → requantize
    → all_gather, EQuARX-style per PAPERS.md) really ships int8 + f32
    per-block scales — ~3.9× fewer wire bytes at block_size=256 instead
    of 2×.  This alias keeps the old call shape (sum semantics, no size
    threshold) and will be removed once callers migrate to
    ``paddle_tpu.distributed.comm``."""
    import warnings
    warnings.warn(
        "all_reduce_quantized is deprecated; use paddle_tpu.distributed"
        ".comm.all_reduce(x, config=CommConfig(dtype='int8')) instead",
        DeprecationWarning, stacklevel=2)
    enforce(2 <= bits <= 8,
            f"all_reduce_quantized supports 2..8 bits (int8 container), "
            f"got {bits}")
    from .comm import CommConfig
    from .comm import all_reduce as _comm_all_reduce
    cfg = CommConfig(dtype="int8", bits=bits, block_size=block_size,
                     min_size_to_compress=0)
    return _comm_all_reduce(x, op=ReduceOp.SUM, group=group, config=cfg)
