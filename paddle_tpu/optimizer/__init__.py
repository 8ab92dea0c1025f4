"""Optimizers (reference: python/paddle/optimizer/*.py and the fused CUDA
optimizer ops in paddle/fluid/operators/optimizers/).

Design: each optimizer has a **functional core** —

    state              = opt.init(params)          # pytree of slots
    new_params, state  = opt.apply_gradients(grads, params, state)

that is pure and jit/pjit/shard_map-safe: under GSPMD, sharding the params
pytree automatically shards the slot pytrees the same way, which is how the
reference's ZeRO-1 optimizer-state sharding (DygraphShardingOptimizer,
fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:28)
falls out for free on TPU (see SURVEY.md A3).

A stateful wrapper (``opt.step(grads)``) gives dygraph-style ergonomics over a
bound Parameter list.  Master-weight (fp32) support mirrors the reference's
multi_precision attr on adam/momentum ops.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..framework.errors import enforce
from ..nn.layer import Parameter
from . import lr as lr  # noqa: F401  (paddle.optimizer.lr namespace)
from .lr import LRScheduler

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adagrad", "RMSProp", "Adam", "AdamW",
    "Lamb", "AdamMax", "lr", "ClipGradByValue", "ClipGradByNorm",
    "ClipGradByGlobalNorm", "global_norm",
]


# ---------------------------------------------------------------------------
# Gradient clipping (reference: python/paddle/fluid/clip.py; the distributed
# cross-group variant lives in paddle_tpu/distributed/fleet/optimizer.py)
# ---------------------------------------------------------------------------
def global_norm(grads) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


class ClipGradByValue:
    def __init__(self, max: float, min: Optional[float] = None):
        self.max = max
        self.min = -max if min is None else min

    def __call__(self, grads):
        return jax.tree_util.tree_map(
            lambda g: jnp.clip(g, self.min, self.max), grads)


class ClipGradByNorm:
    def __init__(self, clip_norm: float):
        self.clip_norm = clip_norm

    def __call__(self, grads):
        def _clip(g):
            n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(n, 1e-12))
            return (g * scale).astype(g.dtype)
        return jax.tree_util.tree_map(_clip, grads)


class ClipGradByGlobalNorm:
    """Reference: fluid/clip.py ClipGradByGlobalNorm.  Under pjit the sum of
    squares is computed on sharded grads and XLA inserts the cross-device
    reductions — no explicit communication needed (unlike the reference's
    HybridParallelClipGrad which allreduces per group)."""

    def __init__(self, clip_norm: float = 1.0):
        self.clip_norm = clip_norm

    def __call__(self, grads):
        norm = global_norm(grads)
        scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(norm, 1e-12))
        return jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)


# ---------------------------------------------------------------------------
# Base optimizer
# ---------------------------------------------------------------------------
def _is_float_param(p) -> bool:
    return jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating)


class Optimizer:
    """Base class. Subclasses implement ``_init_slot(p)`` and
    ``_update(g, p, slots, lr, step)`` operating on single fp32 leaves."""

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision: bool = True,
                 apply_decay_param_fun: Optional[Callable[[str], bool]] = None):
        self._lr = learning_rate
        self._grad_clip = grad_clip
        # weight_decay: float (L2 semantics) or a regularizer instance
        # (reference: optimizer accepts paddle.regularizer.L1Decay/L2Decay)
        from ..regularizer import L1Decay, L2Decay
        self._l1 = 0.0
        if isinstance(weight_decay, L1Decay):
            self._wd, self._l1 = 0.0, weight_decay.coeff
        elif isinstance(weight_decay, L2Decay):
            self._wd = weight_decay.coeff
        else:
            self._wd = float(weight_decay) if weight_decay else 0.0
        self._apply_decay_param_fun = apply_decay_param_fun
        self.multi_precision = multi_precision
        self._parameters = list(parameters) if parameters is not None else None
        self._state = None  # lazily built for the stateful path

    # -- lr ----------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._lr, LRScheduler):
            return self._lr.get_lr()
        return self._lr

    def set_lr(self, value: float):
        enforce(not isinstance(self._lr, LRScheduler),
                "can't set_lr when using an LRScheduler")
        self._lr = value

    def _lr_at(self, step):
        if isinstance(self._lr, LRScheduler):
            return self._lr(step)
        return jnp.asarray(self._lr, jnp.float32)

    # -- functional API ----------------------------------------------------
    def init(self, params) -> Dict[str, Any]:
        """Build the slot-variable pytree for a params pytree."""
        def _master(p):
            if self.multi_precision and _is_float_param(p) and \
                    jnp.asarray(p).dtype != jnp.float32:
                return jnp.asarray(p).astype(jnp.float32)
            return None
        slots = jax.tree_util.tree_map(self._init_slot, params)
        master = jax.tree_util.tree_map(_master, params)
        return {"step": jnp.zeros((), jnp.int32), "slots": slots,
                "master": master}

    def apply_gradients(self, grads, params, state, lr=None):
        """Pure update: returns (new_params, new_state).

        ``lr`` overrides the schedule (used by the stateful path, where the
        paddle convention is that the user drives the scheduler's .step() —
        typically per epoch — rather than the optimizer's iteration count).
        The update's operations carry the scope ``<class>.update``
        (``adamw.update``) in the trace and the lowered HLO."""
        with jax.named_scope(type(self).__name__.lower() + ".update"):
            return self._apply_gradients(grads, params, state, lr)

    def _apply_gradients(self, grads, params, state, lr):
        step = state["step"] + 1
        lr_t = jnp.asarray(lr, jnp.float32) if lr is not None \
            else self._lr_at(step - 1)
        if self._grad_clip is not None:
            grads = self._grad_clip(grads)

        # decide weight decay per-leaf using the key path (dict pytrees keep
        # param names, so apply_decay_param_fun gets real names)
        wd_tree = self._decay_tree(params)

        def _upd(g, p, slots, master, wd, l1):
            if g is None:
                return p, slots, master
            compute_p = master if master is not None else jnp.asarray(p)
            g32 = g.astype(jnp.float32)
            if self._l1:   # L1Decay: lasso penalty as a gradient addition
                g32 = g32 + l1 * jnp.sign(compute_p.astype(jnp.float32))
            new_p32, new_slots = self._update(
                g32, compute_p.astype(jnp.float32), slots, lr_t, step, wd)
            if master is not None:
                return new_p32.astype(jnp.asarray(p).dtype), new_slots, new_p32
            return new_p32.astype(jnp.asarray(p).dtype), new_slots, None

        l1_tree = self._l1_tree(params)
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state["slots"])
        flat_m = treedef.flatten_up_to(state["master"])
        flat_w = treedef.flatten_up_to(wd_tree)
        flat_l1 = treedef.flatten_up_to(l1_tree)
        out = [_upd(g, p, s, m, w, l1) for g, p, s, m, w, l1 in
               zip(flat_g, flat_p, flat_s, flat_m, flat_w, flat_l1)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_slots = treedef.unflatten([o[1] for o in out])
        new_master = treedef.unflatten([o[2] for o in out])
        return new_params, {"step": step, "slots": new_slots,
                            "master": new_master}

    # convenience: one-call pytree update
    def update(self, grads, params, state):
        return self.apply_gradients(grads, params, state)

    def _decay_tree(self, params, coeff=None):
        """Per-leaf decay coefficients (``coeff`` defaults to the L2
        weight decay); apply_decay_param_fun receives the dotted key path
        (real parameter names when params is the state_dict-style dict
        pytree)."""
        coeff = self._wd if coeff is None else coeff
        fn = self._apply_decay_param_fun

        def _path_str(path):
            parts = []
            for k in path:
                if hasattr(k, "key"):
                    parts.append(str(k.key))
                elif hasattr(k, "idx"):
                    parts.append(str(k.idx))
                elif hasattr(k, "name"):
                    parts.append(str(k.name))
            return ".".join(parts)

        return jax.tree_util.tree_map_with_path(
            lambda path, p: coeff if (coeff and (
                fn is None or fn(_path_str(path)))) else 0.0,
            params)

    def _l1_tree(self, params):
        """Per-leaf L1Decay coefficients, gated by the same
        apply_decay_param_fun as L2 decay."""
        return self._decay_tree(params, coeff=self._l1)

    # -- stateful API ------------------------------------------------------
    def _param_keys(self):
        """Stable, unique dict keys carrying real parameter names so
        apply_decay_param_fun / exclude_from_weight_decay_fn see what the
        user's model calls the parameter, not a list index.  Keys are
        snapshotted at first use: name collisions (two models with the same
        architecture) get a #i suffix, and late name assignment can't change
        the pytree structure mid-training."""
        if getattr(self, "_param_key_list", None) is None:
            keys, seen = [], set()
            for i, p in enumerate(self._parameters):
                k = p.name if p.name else f"param_{i}"
                if k in seen:
                    k = f"{k}#{i}"
                seen.add(k)
                keys.append(k)
            self._param_key_list = keys
        return self._param_key_list

    def _ensure_state(self):
        enforce(self._parameters is not None,
                "stateful step() needs parameters= at construction")
        if self._state is None:
            values = dict(zip(self._param_keys(),
                              (p.value for p in self._parameters)))
            self._state = self.init(values)

    def step(self, grads=None):
        """Apply grads (list matching the bound parameters)."""
        self._ensure_state()
        if grads is None:
            grads = [p._grad for p in self._parameters]
        keys = self._param_keys()
        values = dict(zip(keys, (p.value for p in self._parameters)))
        grads = dict(zip(keys, (None if not t.trainable else g
                                for g, t in zip(grads, self._parameters))))
        lr = self.get_lr() if isinstance(self._lr, LRScheduler) else None
        new_values, self._state = self.apply_gradients(
            grads, values, self._state, lr=lr)
        for p, k in zip(self._parameters, keys):
            p.value = new_values[k]
            p._grad = None

    def clear_grad(self):
        if self._parameters:
            for p in self._parameters:
                p._grad = None

    def backward(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None, callbacks=None):
        """Reference Optimizer.backward: compute (param, grad) pairs for
        ``minimize``.  Functionally: grads of ``loss`` — when ``loss`` is
        a CALLABLE of the parameter values it is differentiated directly;
        a plain tensor cannot be walked backward (no tape) and raises
        with the recipe.  Grads are computed for (and later applied to)
        the CONSTRUCTOR-bound parameters; a ``parameters`` argument must
        match that binding — rebinding per call is not supported in the
        stateful path."""
        enforce(self._parameters,
                "optimizer has no bound parameters; construct with "
                "parameters=... (the stateful step/minimize path is "
                "bound at construction)")
        if parameters is not None:
            enforce(list(parameters) == list(self._parameters),
                    "minimize/backward(parameters=...) must match the "
                    "constructor-bound parameter list — per-call "
                    "rebinding is not supported")
        if not callable(loss):
            raise RuntimeError(
                "Optimizer.backward(loss_tensor) needs an autograd tape, "
                "which does not exist here; pass a CALLABLE "
                "loss_fn(values_dict) (or use jax.value_and_grad "
                "directly — docs/MIGRATION.md: autograd).")
        keys = self._param_keys()
        values = dict(zip(keys, (p.value for p in self._parameters)))
        grads = jax.grad(loss)(values)
        return [(p, grads[k]) for p, k in zip(self._parameters, keys)]

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Reference Optimizer.minimize: backward + apply.  ``loss`` is a
        callable of the parameter-values dict (see backward)."""
        pg = self.backward(loss, parameters=parameters)
        self.step([g for _, g in pg])
        return None, pg

    def append_regularization_ops(self, params_grads, regularization=None):
        """Reference append_regularization_ops: add the regularizer's
        gradient term to each grad (decay is otherwise folded into
        _update at apply time)."""
        coeff = getattr(regularization, "coeff", None)
        if coeff is None:
            return params_grads
        from ..regularizer import L1Decay
        if isinstance(regularization, L1Decay):
            return [(p, g + coeff * jnp.sign(jnp.asarray(p)))
                    for p, g in params_grads]
        return [(p, g + coeff * jnp.asarray(p)) for p, g in params_grads]

    def get_opti_var_name_list(self):
        """Slot-variable names (reference get_opti_var_name_list)."""
        self._ensure_state()
        names = []
        for pname, slot in self._state["slots"].items():
            if isinstance(slot, dict):   # slotless optimizers (SGD): None
                names += [f"{pname}.{s}" for s in slot]
        return names

    def state_dict(self):
        self._ensure_state()
        sd = {"state": self._state}
        if isinstance(self._lr, LRScheduler):
            sd["lr"] = self._lr.state_dict()
        return sd

    def set_state_dict(self, sd):
        self._state = sd["state"]
        if isinstance(self._lr, LRScheduler) and "lr" in sd:
            self._lr.set_state_dict(sd["lr"])

    # -- subclass hooks ----------------------------------------------------
    def _init_slot(self, p):
        return ()

    def _update(self, g, p, slots, lr, step, wd):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Concrete rules (fp32 math; reference operators/optimizers/*_op.cc semantics)
# ---------------------------------------------------------------------------
class SGD(Optimizer):
    def _update(self, g, p, slots, lr, step, wd):
        if wd:
            g = g + wd * p
        return p - lr * g, slots


class Momentum(Optimizer):
    """Reference momentum_op: velocity = mu*velocity + grad;
    param -= lr * (grad + mu*velocity) if nesterov else lr*velocity."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _init_slot(self, p):
        return {"velocity": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        if wd:
            g = g + wd * p
        v = self.momentum * slots["velocity"] + g
        if self.use_nesterov:
            new_p = p - lr * (g + self.momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.epsilon = epsilon

    def _init_slot(self, p):
        return {"moment": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        if wd:
            g = g + wd * p
        m = slots["moment"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self.epsilon), {"moment": m}


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6,
                 momentum=0.0, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.rho, self.epsilon, self.momentum = rho, epsilon, momentum

    def _init_slot(self, p):
        # separate arrays per slot: donation-safe (a shared buffer would be
        # donated twice in a donated train step)
        return {"mean_square": jnp.zeros_like(jnp.asarray(p), jnp.float32),
                "momentum": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        if wd:
            g = g + wd * p
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * jnp.square(g)
        mom = self.momentum * slots["momentum"] + lr * g / jnp.sqrt(ms + self.epsilon)
        return p - mom, {"mean_square": ms, "momentum": mom}


class Adam(Optimizer):
    """Reference adam_op.cc (L2-coupled weight decay via weight_decay arg)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, lazy_mode=False,
                 apply_decay_param_fun=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, apply_decay_param_fun)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self._decoupled = False

    def _init_slot(self, p):
        return {"moment1": jnp.zeros_like(jnp.asarray(p), jnp.float32),
                "moment2": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        if wd and not self._decoupled:
            g = g + wd * p
        t = step.astype(jnp.float32)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + self.epsilon)
        if wd and self._decoupled:
            new_p = new_p - lr * wd * p
        return new_p, {"moment1": m, "moment2": v}


class AdamW(Adam):
    """Decoupled weight decay (reference adamw_op / python adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 grad_clip=None, multi_precision=True,
                 apply_decay_param_fun=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, multi_precision,
                         apply_decay_param_fun=apply_decay_param_fun)
        self._decoupled = True


class AdamMax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _init_slot(self, p):
        return {"moment": jnp.zeros_like(jnp.asarray(p), jnp.float32),
                "inf_norm": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        if wd:
            g = g + wd * p
        t = step.astype(jnp.float32)
        m = self.beta1 * slots["moment"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["inf_norm"], jnp.abs(g))
        new_p = p - lr / (1 - self.beta1 ** t) * m / (u + self.epsilon)
        return new_p, {"moment": m, "inf_norm": u}


class Lamb(Optimizer):
    """Reference lamb_op.cc / distributed_fused_lamb_op.cu semantics: adam
    update direction scaled by trust ratio ||p|| / ||update||."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=True):
        # exclude_from_weight_decay_fn(name) -> True means wd=0 for that param
        # (reference lamb excludes LayerNorm/bias params; inverted polarity vs
        # apply_decay_param_fun, which selects params that DO get decay).
        apply_fn = None
        if exclude_from_weight_decay_fn is not None:
            apply_fn = lambda name: not exclude_from_weight_decay_fn(name)
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, multi_precision,
                         apply_decay_param_fun=apply_fn)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def _init_slot(self, p):
        return {"moment1": jnp.zeros_like(jnp.asarray(p), jnp.float32),
                "moment2": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        t = step.astype(jnp.float32)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        update = mhat / (jnp.sqrt(vhat) + self.epsilon) + wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        u_norm = jnp.sqrt(jnp.sum(jnp.square(update)))
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        return p - lr * trust * update, {"moment1": m, "moment2": v}


class Lars(Optimizer):
    """LARS (reference operators/optimizers/lars_momentum_op.cc +
    fleet lars meta-optimizer): momentum SGD with a layerwise-adaptive
    learning rate — local_lr = lars_coeff * ||p|| / (||g|| + wd*||p|| + eps).
    The large-batch ResNet optimizer."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, epsilon=1e-9, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=True):
        apply_fn = None
        if exclude_from_weight_decay_fn is not None:
            apply_fn = lambda name: not exclude_from_weight_decay_fn(name)
        super().__init__(learning_rate, parameters, lars_weight_decay,
                         grad_clip, multi_precision,
                         apply_decay_param_fun=apply_fn)
        self.momentum = momentum
        self.lars_coeff = lars_coeff
        self.epsilon = epsilon

    def _init_slot(self, p):
        return {"velocity": jnp.zeros_like(jnp.asarray(p), jnp.float32)}

    def _update(self, g, p, slots, lr, step, wd):
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self.lars_coeff * w_norm / (g_norm + wd * w_norm + self.epsilon),
            1.0)
        v = (self.momentum * slots["velocity"]
             + lr * local_lr * (g + wd * p))
        return p - v, {"velocity": v}


__all__.append("Lars")


class Adadelta(Optimizer):
    """Reference adadelta_op: accumulated-gradient / accumulated-update
    adaptive steps; no explicit learning-rate dependence beyond scaling."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.epsilon, self.rho = epsilon, rho

    def _init_slot(self, p):
        z = jnp.zeros_like(jnp.asarray(p), jnp.float32)
        return {"avg_squared_grad": z, "avg_squared_update": z}

    def _update(self, g, p, slots, lr, step, wd):
        if wd:
            g = g + wd * p
        eg = self.rho * slots["avg_squared_grad"] + (1 - self.rho) * jnp.square(g)
        upd = (jnp.sqrt(slots["avg_squared_update"] + self.epsilon)
               / jnp.sqrt(eg + self.epsilon)) * g
        eu = self.rho * slots["avg_squared_update"] + (1 - self.rho) * jnp.square(upd)
        return p - lr * upd, {"avg_squared_grad": eg,
                              "avg_squared_update": eu}


Adamax = AdamMax      # reference spells the public class "Adamax"
__all__ += ["Adadelta", "Adamax"]
