"""ZeRO-1 weight-update sharding: :class:`ShardedOptimizer`.

Reference: ``DygraphShardingOptimizer``
(fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:28)
assigns whole parameters to ranks; the TPU-native form (PAPERS.md
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training") shards a padded flat view instead, which makes every shape
even by construction:

    pack     params → fp32 flat master, zero-padded to n·alignment
    sync     reduce-scatter the flat gradient along the dp axis
             (exact psum_scatter, or the comm package's int8 two-phase)
    update   inner optimizer's elementwise rule on MY (flat_len/n,)
             shard of master + slots — 1/n of the Adam state per replica
    gather   all-gather the updated flat master, unpack to leaves

Two execution modes, one state layout (global flat leaves are
``(padded_len,)`` sharded along the axis):

- **shard_map** (the axis is bound in the current trace): explicit
  collectives; state leaves are the per-rank ``(chunk,)`` view.  Call
  ``init`` inside the same shard_map (out_specs from
  :meth:`state_sharding_specs`).
- **jit/GSPMD** (mesh exists, axis unbound — the hapi path): sharding
  constraints on the flat state make XLA derive the same
  reduce-scatter + sharded update + all-gather.
- no mesh at all → plain single-replica flat update (numerics identical
  to the inner optimizer).

Only *elementwise* update rules shard this way (Adam/AdamW/SGD/
Momentum/...); trust-ratio optimizers (Lamb, Lars) need per-parameter
norms a flat shard cannot see and are rejected at construction.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...framework.errors import enforce
from ..collective import _in_axis, bound_axis_size
from ..topology import get_mesh
from .collectives import _account, _int8_reduce_scatter_flat
from .config import CommConfig, resolve_comm_config

__all__ = ["ShardedOptimizer", "repack_flat"]


def repack_flat(saved, target_len: int) -> np.ndarray:
    """Re-pad a zero-padded flat pack (the ZeRO-1 master / slot layout)
    from one shard count's alignment to another's — the elastic-resize
    relayout (ISSUE 9).

    The pack invariant makes this exact: real elements occupy
    ``[0, total)`` and everything past ``total`` is zeros, so moving
    between ``padded_old`` and ``padded_new`` (both ≥ total) only drops
    or adds zero padding — the real elements are preserved **bitwise**.
    Dropping a nonzero tail is refused loudly: that would mean the
    target was packed for different params, not a different width.
    """
    saved = np.asarray(saved)
    enforce(saved.ndim == 1,
            f"repack_flat wants a flat (1-D) pack, got {saved.shape}")
    n = saved.shape[0]
    target_len = int(target_len)
    if target_len == n:
        return saved
    if target_len < n:
        tail = saved[target_len:]
        enforce(not np.any(tail),
                f"repack_flat would drop {int(np.count_nonzero(tail))} "
                f"nonzero element(s) truncating {n} -> {target_len}; the "
                f"saved pack belongs to different params")
        return np.ascontiguousarray(saved[:target_len])
    return np.concatenate(
        [saved, np.zeros((target_len - n,), saved.dtype)])


class _LeafInfo(NamedTuple):
    index: int          # position in the flattened params leaf list
    path: str           # dotted key path (for decay gating / debugging)
    shape: Tuple[int, ...]
    dtype: Any
    size: int
    offset: int         # into the flat vector


class _PackMeta(NamedTuple):
    treedef: Any
    n_leaves: int
    packed: Tuple[_LeafInfo, ...]
    total: int          # packed elements before padding
    padded: int         # after padding (divisible by n·alignment)
    chunk: int          # padded // n


def _path_str(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return ".".join(parts)


class ShardedOptimizer:
    """ZeRO-1 wrapper with the framework optimizer's functional
    contract (``init(params)`` / ``apply_gradients(grads, params,
    state, lr=None)``) plus a dygraph-style ``step``.

    Args:
        inner: an elementwise framework optimizer (Adam, AdamW, SGD,
            Momentum, ...).
        axis: mesh axis to shard along; default "sharding" when the
            mesh has it, else "dp".
        num_shards: override the shard count (otherwise resolved from
            the bound axis or the installed mesh; 1 with no mesh).
        comm: optional :class:`CommConfig` compressing the gradient
            reduce-scatter (shard_map mode only; error feedback is the
            per-leaf :func:`sync_gradients` path's job and is rejected
            here — a sharded residual would change the EF semantics).
        grad_op: "avg" (dp convention, default) or "sum" — how local
            gradients combine across the axis in shard_map mode.  Under
            GSPMD the mean over the global batch already happened in
            the loss.
    """

    def __init__(self, inner, axis: Optional[str] = None,
                 num_shards: Optional[int] = None, comm=None,
                 grad_op: str = "avg", mesh=None):
        from ...optimizer import (Adam, Adagrad, Adadelta, AdamMax,
                                  ClipGradByNorm, Momentum, RMSProp, SGD)
        enforce(isinstance(inner, (Adam, Adagrad, Adadelta, AdamMax,
                                   Momentum, RMSProp, SGD)),
                f"ShardedOptimizer needs an elementwise optimizer "
                f"(Adam/AdamW/SGD/Momentum/...); {type(inner).__name__} "
                f"updates through cross-element statistics a flat shard "
                f"cannot see")
        enforce(not isinstance(getattr(inner, "_grad_clip", None),
                               ClipGradByNorm),
                "ClipGradByNorm clips per-parameter norms, which a flat "
                "shard cannot see; use ClipGradByGlobalNorm or "
                "ClipGradByValue")
        self._inner = inner
        self._axis_opt = axis
        self._mesh_opt = mesh
        self._num_shards_opt = num_shards
        cfg = resolve_comm_config(comm) if comm is not None else None
        if cfg is not None:
            enforce(not cfg.error_feedback,
                    "error feedback needs a per-replica residual that "
                    "ZeRO's sharded state does not carry; use "
                    "comm.sync_gradients for EF gradient sync")
            enforce(cfg.dtype != "bfloat16",
                    "bf16 reduce-scatter would down-cast the master "
                    "gradient; use int8 (blockwise scales) or exact")
        self._comm = cfg
        enforce(grad_op in ("avg", "sum"),
                f"grad_op must be 'avg' or 'sum', got {grad_op!r}")
        self._grad_op = grad_op
        self._bound: Optional[Tuple[Any, str, int]] = None
        self._zstate = None     # dygraph-style step() state
        # leaf index -> the NamedSharding each parameter was placed with
        # when init() saw it (GSPMD mode): where the updated parameters
        # must come back to
        self._placed: Dict[int, NamedSharding] = {}

    # -- delegation ---------------------------------------------------------
    @property
    def inner(self):
        return self._inner

    def __getattr__(self, name):
        if name.startswith("__") or name == "_inner":
            raise AttributeError(name)
        return getattr(self._inner, name)

    # -- topology -----------------------------------------------------------
    def bind_mesh(self, mesh=None) -> "ShardedOptimizer":
        """(Re)resolve the mesh/axis/shard-count binding — hapi's
        ``prepare`` calls this so the fleet mesh active at prepare time
        is the one the jitted step constrains against."""
        if mesh is not None:
            self._mesh_opt = mesh
        self._bound = None
        self._resolve()
        return self

    def _resolve(self) -> Tuple[Any, str, int]:
        if self._bound is not None:
            return self._bound
        mesh = self._mesh_opt if self._mesh_opt is not None else get_mesh()
        axis = self._axis_opt
        if axis is None:
            axis = ("sharding" if mesh is not None
                    and "sharding" in mesh.axis_names
                    and mesh.shape["sharding"] > 1 else "dp")
        n = self._num_shards_opt
        if n is None:
            if _in_axis(axis):
                n = int(bound_axis_size(axis))
            elif mesh is not None and axis in mesh.axis_names:
                n = int(mesh.shape[axis])
            else:
                n = 1
        self._bound = (mesh, axis, int(n))
        return self._bound

    @property
    def num_shards(self) -> int:
        return self._resolve()[2]

    @property
    def axis(self) -> str:
        return self._resolve()[1]

    # -- packing ------------------------------------------------------------
    def _alignment(self, n: int) -> int:
        return n * (self._comm.block_size if self._comm is not None
                    and self._comm.dtype == "int8" else 1)

    def _meta(self, params) -> _PackMeta:
        flat_wp, treedef = jax.tree_util.tree_flatten_with_path(params)
        packed: List[_LeafInfo] = []
        offset = 0
        for i, (path, leaf) in enumerate(flat_wp):
            arr = jnp.asarray(leaf)
            if not jnp.issubdtype(arr.dtype, jnp.floating):
                continue            # non-float leaves pass through as-is
            size = int(np.prod(arr.shape)) if arr.ndim else 1
            packed.append(_LeafInfo(i, _path_str(path), tuple(arr.shape),
                                    arr.dtype, size, offset))
            offset += size
        _, _, n = self._resolve()
        align = self._alignment(n)
        padded = -(-max(offset, 1) // align) * align
        return _PackMeta(treedef, len(flat_wp), tuple(packed), offset,
                         padded, padded // n)

    def _pack_flat(self, leaves, meta: _PackMeta,
                   fill_missing: bool = False) -> jnp.ndarray:
        present = [leaves[info.index] for info in meta.packed
                   if leaves[info.index] is not None]
        # concrete leaves (init time) pack on the HOST: leaves of a
        # TP-placed model carry mixed shardings, which an eager
        # concatenate miscompiles on this stack (observed: replicated LN
        # weights summed across devices), and a device-side pack would
        # build the whole fp32 master on the default device.  Traced
        # packs (the jitted step) are resharded by the partitioner.
        xp = (np if present and not any(isinstance(l, jax.core.Tracer)
                                        for l in present) else jnp)
        parts = []
        for info in meta.packed:
            leaf = leaves[info.index]
            if leaf is None:
                enforce(fill_missing,
                        f"missing leaf for {info.path} in pack")
                parts.append(xp.zeros((info.size,), xp.float32))
            else:
                parts.append(xp.ravel(xp.asarray(leaf)).astype(xp.float32))
        pad = meta.padded - meta.total
        if pad or not parts:
            parts.append(xp.zeros((pad,), xp.float32))
        return xp.concatenate(parts) if len(parts) > 1 else parts[0]

    def _unpack(self, flat, meta: _PackMeta, params, placed=None):
        """Flat master -> parameter leaves.  ``placed`` (leaf index ->
        NamedSharding) pins each leaf back to its parameter's own layout:
        left to propagation, a leaf sliced out of the axis-sharded flat
        stays sharded like the flat, so the step returns every parameter
        in a layout other than the one it arrived in — no aliasing with
        the donated input, a second compile on the next call, and the
        parameter all-gather that ZeRO-1 ends a step with never emitted."""
        leaves = list(meta.treedef.flatten_up_to(params))
        for info in meta.packed:
            seg = lax.slice(flat, (info.offset,),
                            (info.offset + info.size,))
            leaf = seg.reshape(info.shape).astype(info.dtype)
            if placed and info.index in placed:
                leaf = lax.with_sharding_constraint(leaf,
                                                    placed[info.index])
            leaves[info.index] = leaf
        return jax.tree_util.tree_unflatten(meta.treedef, leaves)

    def _coeff_flat(self, params, meta: _PackMeta, tree):
        """Static per-leaf coefficient tree (decay / L1) in the pack
        layout: a scalar when every leaf shares one coefficient (the
        padding holds zeros, which any coefficient leaves at zero), else
        a ``(padded,)`` vector built on device from one broadcast per
        run of equal coefficients — never a host constant the size of
        the model baked into the program."""
        leaves = meta.treedef.flatten_up_to(tree)
        runs: List[List[float]] = []        # [coefficient, length]
        for info in meta.packed:
            c = float(leaves[info.index])
            if runs and runs[-1][0] == c:
                runs[-1][1] += info.size
            else:
                runs.append([c, info.size])
        if len(runs) <= 1:
            return jnp.float32(runs[0][0] if runs else 0.0)
        runs.append([0.0, meta.padded - meta.total])
        return jnp.concatenate([jnp.full((int(n),), c, jnp.float32)
                                for c, n in runs if n])

    # -- functional contract ------------------------------------------------
    def init(self, params) -> Dict[str, Any]:
        """Flat sharded state: ``{"step", "flat" (fp32 master shard),
        "slots" {name: shard}}``.  Inside ``shard_map`` the leaves are
        this rank's ``(chunk,)`` slice; on the host they are the full
        ``(padded,)`` vectors, placed sharded when a mesh is
        installed."""
        mesh, axis, n = self._resolve()
        meta = self._meta(params)
        leaves = meta.treedef.flatten_up_to(params)
        flat = self._pack_flat(leaves, meta)
        if _in_axis(axis):
            idx = lax.axis_index(axis)
            flat = lax.dynamic_slice(flat, (idx * meta.chunk,),
                                     (meta.chunk,))
        elif mesh is not None and n > 1 and axis in mesh.axis_names:
            self._placed = {
                info.index: leaves[info.index].sharding
                for info in meta.packed
                if isinstance(getattr(leaves[info.index], "sharding", None),
                              NamedSharding)
                and leaves[info.index].sharding.mesh == mesh}
            # place the master BEFORE deriving the slots from it: slots
            # built from an unplaced master are whole copies on the
            # default device (3x the model in fp32 on device 0)
            flat = jax.device_put(flat, NamedSharding(mesh, P(axis)))
        flat = jnp.asarray(flat)
        return {"step": jnp.zeros((), jnp.int32), "flat": flat,
                "slots": self._inner._init_slot(flat)}

    def relayout_state(self, state, params):
        """Re-pack a (host or globally-gathered) ZeRO-1 state built for a
        DIFFERENT shard count onto this optimizer's currently-resolved
        mesh/axis/shard-count binding — the elastic dp-resize path
        (ISSUE 9).  ``state`` leaves must be the full ``(padded_old,)``
        vectors (what a checkpoint restore without a sharded template
        yields); returns the state placed for the current mesh.  Values
        are preserved bitwise (only zero padding moves)."""
        mesh, axis, n = self._resolve()
        meta = self._meta(params)

        def _repack(leaf):
            leaf = np.asarray(leaf)
            if leaf.ndim != 1:
                return jnp.asarray(leaf)      # "step" scalar passthrough
            enforce(leaf.shape[0] >= meta.total,
                    f"flat state of {leaf.shape[0]} elements cannot hold "
                    f"{meta.total} packed params — wrong checkpoint?")
            return jnp.asarray(repack_flat(leaf, meta.padded))

        out = {"step": jnp.asarray(np.asarray(state["step"]), jnp.int32),
               "flat": _repack(state["flat"]),
               "slots": jax.tree_util.tree_map(_repack, state["slots"])}
        if mesh is not None and n > 1 and axis in mesh.axis_names:
            shard = NamedSharding(mesh, P(axis))
            out["flat"] = jax.device_put(out["flat"], shard)
            out["slots"] = jax.tree_util.tree_map(
                lambda s: jax.device_put(s, shard), out["slots"])
        return out

    def state_sharding_specs(self, params=None):
        """PartitionSpecs for the state pytree — the out_specs/in_specs
        a ``shard_map`` drill threads the state through."""
        _, axis, _ = self._resolve()
        slots = self._inner._init_slot(jnp.zeros((1,), jnp.float32))
        return {"step": P(),
                "flat": P(axis),
                "slots": jax.tree_util.tree_map(lambda _: P(axis), slots)}

    def _clip_scale(self, flat_g, axis: str, sharded: bool):
        """ClipGradByGlobalNorm over the *synced* gradient: local
        shard's sum of squares + one scalar psum."""
        from ...optimizer import ClipGradByGlobalNorm, ClipGradByValue
        clip = getattr(self._inner, "_grad_clip", None)
        if clip is None:
            return flat_g
        if isinstance(clip, ClipGradByValue):
            return jnp.clip(flat_g, clip.min, clip.max)
        if isinstance(clip, ClipGradByGlobalNorm):
            sq = jnp.sum(jnp.square(flat_g))
            if sharded:
                sq = lax.psum(sq, axis)
            norm = jnp.sqrt(sq)
            scale = jnp.minimum(1.0, clip.clip_norm
                                / jnp.maximum(norm, 1e-12))
            return flat_g * scale
        raise TypeError(f"unsupported grad clip {type(clip).__name__} "
                        f"for ShardedOptimizer")

    def apply_gradients(self, grads, params, state, lr=None):
        """Pure ZeRO-1 update: (new_params, new_state).  ``grads`` are
        the LOCAL gradients in shard_map mode (the sync happens here,
        compressed when configured); already-global under GSPMD."""
        inner = self._inner
        mesh, axis, n = self._resolve()
        meta = self._meta(params)
        sharded = _in_axis(axis)
        if sharded:
            enforce(int(bound_axis_size(axis)) == n,
                    f"bound axis {axis} has size {bound_axis_size(axis)} "
                    f"but state was built for {n} shards")
        # the scopes name the three legs in the trace and the HLO; the
        # all-to-all and all-gathers GSPMD inserts are its re-layouts of
        # what each leg produces
        with jax.named_scope("zero.pack"):
            g_leaves = meta.treedef.flatten_up_to(grads)
            flat_g = self._pack_flat(g_leaves, meta, fill_missing=True)

            step = state["step"] + 1
            lr_t = (jnp.asarray(lr, jnp.float32) if lr is not None
                    else inner._lr_at(step - 1))
            wd_flat = self._coeff_flat(params, meta, inner._decay_tree(params))
            l1_flat = (self._coeff_flat(params, meta, inner._l1_tree(params))
                       if getattr(inner, "_l1", 0.0) else None)

            def my_chunk(coeff):
                # this rank's slice of a per-element coefficient vector
                if coeff is None or coeff.ndim == 0:
                    return coeff
                return lax.dynamic_slice(
                    coeff, (lax.axis_index(axis) * meta.chunk,), (meta.chunk,))

            if sharded:
                if self._comm is not None and self._comm.dtype == "int8":
                    _account(meta.padded, self._comm, rounds=1)
                    g_shard, _own = _int8_reduce_scatter_flat(
                        flat_g, axis, self._comm, self._grad_op)
                else:
                    _account(meta.padded, CommConfig(), rounds=1)
                    g_shard = lax.psum_scatter(flat_g, axis,
                                               scatter_dimension=0, tiled=True)
                    if self._grad_op == "avg":
                        g_shard = g_shard / n
                wd, l1 = my_chunk(wd_flat), my_chunk(l1_flat)
            else:
                g_shard, wd, l1 = flat_g, wd_flat, l1_flat
                if mesh is not None and n > 1 and axis in mesh.axis_names:
                    cons = NamedSharding(mesh, P(axis))
                    g_shard = lax.with_sharding_constraint(g_shard, cons)

        with jax.named_scope("zero.update"):
            g_shard = self._clip_scale(g_shard, axis, sharded)
            p_shard = state["flat"]
            if l1 is not None:
                g_shard = g_shard + l1 * jnp.sign(p_shard)
            # weight decay as a flat vector: the inner's scalar-wd branches
            # (`if wd`) can't take one, so reproduce its two decay modes
            # around a wd=0 update — coupled (L2 into the gradient) before,
            # decoupled (AdamW's -lr·wd·p) after
            decoupled = bool(getattr(inner, "_decoupled", False))
            if not decoupled:
                g_shard = g_shard + wd * p_shard
            new_shard, new_slots = inner._update(
                g_shard, p_shard, state["slots"], lr_t, step, 0.0)
            if decoupled:
                new_shard = new_shard - lr_t * wd * p_shard

        with jax.named_scope("zero.unpack"):
            if sharded:
                _account(meta.padded, CommConfig(), rounds=1)  # param gather
                full = lax.all_gather(new_shard, axis, axis=0, tiled=True)
            else:
                full = new_shard
                if mesh is not None and n > 1 and axis in mesh.axis_names:
                    full = lax.with_sharding_constraint(
                        full, NamedSharding(mesh, P(axis)))
            new_params = self._unpack(full, meta, params,
                                      placed=None if sharded else self._placed)
        return new_params, {"step": step, "flat": new_shard,
                            "slots": new_slots}

    def update(self, grads, params, state):
        return self.apply_gradients(grads, params, state)

    # -- stateful (dygraph-parity) path -------------------------------------
    def step(self, grads=None):
        """Eager convenience over the inner's bound parameters (GSPMD/
        single-replica modes; a shard_map drill drives the functional
        contract directly)."""
        from ...optimizer import LRScheduler
        inner = self._inner
        enforce(inner._parameters is not None,
                "stateful step() needs parameters= at construction")
        keys = inner._param_keys()
        if grads is None:
            grads = [p._grad for p in inner._parameters]
        values = dict(zip(keys, (p.value for p in inner._parameters)))
        gdict = dict(zip(keys, (None if not t.trainable else g
                                for g, t in zip(grads, inner._parameters))))
        if self._zstate is None:
            self._zstate = self.init(values)
        lr = inner.get_lr() if isinstance(inner._lr, LRScheduler) else None
        new_values, self._zstate = self.apply_gradients(
            gdict, values, self._zstate, lr=lr)
        for p, k in zip(inner._parameters, keys):
            p.value = new_values[k]
            p._grad = None

    def clear_grad(self):
        self._inner.clear_grad()
