"""Automatic mixed precision (reference: python/paddle/amp/ — auto_cast.py:21
``auto_cast``, :81 ``decorate``; grad_scaler.py:26 ``GradScaler``; on-device
finite check + scale update ops paddle/fluid/operators/amp/
check_finite_and_unscale_op.cc and update_loss_scaling_op.cc).

TPU defaults to bfloat16, where loss scaling is unnecessary — but the full
dynamic-loss-scaling state machine is implemented (and jit-safe) for fp16
parity.  See SURVEY.md A8.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework.dtype import convert_dtype
from ..framework.errors import enforce
from . import state as _state
from .state import BLACK_OPS, WHITE_OPS  # noqa: F401

__all__ = ["auto_cast", "decorate", "GradScaler", "amp_guard",
           "is_bfloat16_supported", "is_float16_supported"]


@contextlib.contextmanager
def auto_cast(enable: bool = True, custom_white_list=None,
              custom_black_list=None, level: str = "O1",
              dtype: str = "bfloat16"):
    """Context under which white-listed ops run in low precision."""
    added_w = set(custom_white_list or ()) - WHITE_OPS
    added_b = set(custom_black_list or ()) - BLACK_OPS
    WHITE_OPS.update(added_w)
    BLACK_OPS.update(added_b)
    prev = _state.push(enable, level, convert_dtype(dtype))
    try:
        yield
    finally:
        _state.pop(prev)
        WHITE_OPS.difference_update(added_w)
        BLACK_OPS.difference_update(added_b)


amp_guard = auto_cast  # legacy alias (fluid.dygraph.amp.amp_guard)


def decorate(models, optimizers=None, level: str = "O2", dtype: str = "bfloat16",
             master_weight: Optional[bool] = None):
    """O2 decoration: cast model params to the low dtype; optimizers keep fp32
    master weights (multi_precision, on by default)."""
    if level not in ("O1", "O2"):
        raise ValueError("level must be O1 or O2")
    if level == "O2":
        single = not isinstance(models, (list, tuple))
        for m in ([models] if single else models):
            m.astype(convert_dtype(dtype))
    if optimizers is not None:
        opts = optimizers if isinstance(optimizers, (list, tuple)) else [optimizers]
        for o in opts:
            if master_weight is not False:
                o.multi_precision = True
        return models, optimizers
    return models


class GradScaler:
    """Dynamic loss scaling (reference amp/grad_scaler.py:26).

    Functional API (jit-safe, the TPU path):
        st = scaler.init_state()
        scaled = scaler.scale_value(loss, st)
        grads, found_inf = scaler.unscale_and_check(grads, st)
        new_st = scaler.update_state(st, found_inf)
        # skip the optimizer update where found_inf via jnp.where / lax.cond

    Stateful API (eager parity): scale(), step(), minimize(), update().
    """

    def __init__(self, enable: bool = True, init_loss_scaling: float = 2.0 ** 15,
                 incr_ratio: float = 2.0, decr_ratio: float = 0.5,
                 incr_every_n_steps: int = 1000,
                 decr_every_n_nan_or_inf: int = 2,
                 use_dynamic_loss_scaling: bool = True):
        self._enable = enable
        self.init_loss_scaling = init_loss_scaling
        self.incr_ratio = incr_ratio
        self.decr_ratio = decr_ratio
        self.incr_every_n_steps = incr_every_n_steps
        self.decr_every_n_nan_or_inf = decr_every_n_nan_or_inf
        self.use_dynamic = use_dynamic_loss_scaling
        self._st = self.init_state()
        self._already_unscaled = False

    def is_enable(self) -> bool:
        return self._enable

    # -- functional -------------------------------------------------------
    def init_state(self):
        return {
            "scale": jnp.asarray(self.init_loss_scaling if self._enable else 1.0,
                                 jnp.float32),
            "good": jnp.zeros((), jnp.int32),
            "bad": jnp.zeros((), jnp.int32),
        }

    def scale_value(self, loss, state):
        if not self._enable:
            return loss
        return loss * state["scale"].astype(loss.dtype)

    def unscale_and_check(self, grads, state):
        """check_finite_and_unscale op semantics: unscale all grads, report a
        single found_inf flag (reference operators/amp/
        check_finite_and_unscale_op.cc)."""
        if not self._enable:
            return grads, jnp.zeros((), jnp.bool_)
        inv = 1.0 / state["scale"]
        unscaled = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), grads)
        finite = jnp.array(True)
        for g in jax.tree_util.tree_leaves(unscaled):
            finite = finite & jnp.all(jnp.isfinite(g.astype(jnp.float32)))
        return unscaled, ~finite

    def update_state(self, state, found_inf):
        """update_loss_scaling op semantics (reference operators/amp/
        update_loss_scaling_op.cc)."""
        if not self._enable or not self.use_dynamic:
            return state
        scale, good, bad = state["scale"], state["good"], state["bad"]
        bad_n = jnp.where(found_inf, bad + 1, 0)
        good_n = jnp.where(found_inf, 0, good + 1)
        decr = bad_n >= self.decr_every_n_nan_or_inf
        incr = good_n >= self.incr_every_n_steps
        new_scale = jnp.where(decr, jnp.maximum(scale * self.decr_ratio, 1.0),
                              jnp.where(incr, scale * self.incr_ratio, scale))
        return {"scale": new_scale,
                "good": jnp.where(incr, 0, good_n),
                "bad": jnp.where(decr, 0, bad_n)}

    # -- stateful (eager) -------------------------------------------------
    def scale(self, value):
        return self.scale_value(value, self._st)

    def step(self, optimizer, grads=None):
        """Unscale, check, conditionally step, update the scale.  If
        ``unscale_(optimizer)`` already ran this iteration (the
        grad-clipping idiom), grads are NOT unscaled a second time —
        the reference tracks the same per-iteration state."""
        if not self._enable:
            optimizer.step(grads)
            return
        if grads is None:
            # paddle-canonical scaler.step(optimizer): pull the grads the
            # user attached to the bound parameters so they get unscaled too
            grads = [p._grad for p in optimizer._parameters]
        if self._already_unscaled:
            found_inf = jnp.asarray(not all(
                bool(jnp.all(jnp.isfinite(g))) for g in grads
                if g is not None))
            unscaled = grads
        else:
            unscaled, found_inf = self.unscale_and_check(grads, self._st)
        if not bool(found_inf):
            optimizer.step(unscaled)
        else:
            optimizer.clear_grad()
        self._st = self.update_state(self._st, found_inf)
        self._already_unscaled = False

    def unscale_(self, optimizer=None):
        """Eager-path unscale of the bound optimizer's param grads
        (reference GradScaler.unscale_, the grad-clip idiom); the
        following step() will not unscale again.  The jit path uses
        unscale_and_check."""
        params = getattr(optimizer, "_parameters", None) or []
        inv = 1.0 / float(self._st["scale"])
        for p in params:
            if getattr(p, "_grad", None) is not None:
                p._grad = p._grad * inv
        self._already_unscaled = True
        return optimizer

    # -- accessor tail (reference amp/grad_scaler.py) ---------------------
    def is_use_dynamic_loss_scaling(self):
        return self.use_dynamic

    def get_init_loss_scaling(self):
        return float(self.init_loss_scaling)

    def set_init_loss_scaling(self, v):
        self.init_loss_scaling = float(v)
        self._st = self.init_state()

    def get_incr_ratio(self):
        return self.incr_ratio

    def set_incr_ratio(self, v):
        enforce(v > 1.0, "incr_ratio must be > 1")
        self.incr_ratio = float(v)

    def get_decr_ratio(self):
        return self.decr_ratio

    def set_decr_ratio(self, v):
        enforce(0.0 < v < 1.0, "decr_ratio must be in (0, 1)")
        self.decr_ratio = float(v)

    def get_incr_every_n_steps(self):
        return self.incr_every_n_steps

    def set_incr_every_n_steps(self, v):
        self.incr_every_n_steps = int(v)

    def get_decr_every_n_nan_or_inf(self):
        return self.decr_every_n_nan_or_inf

    def set_decr_every_n_nan_or_inf(self, v):
        self.decr_every_n_nan_or_inf = int(v)

    def minimize(self, optimizer, scaled_loss=None, grads=None):
        self.step(optimizer, grads)

    def update(self):
        pass  # folded into step()

    def get_loss_scaling(self):
        return float(self._st["scale"])

    def state_dict(self):
        return dict(self._st)

    def load_state_dict(self, sd):
        self._st = dict(sd)


def is_bfloat16_supported(device=None) -> bool:
    """bf16 is the native TPU compute dtype; CPU XLA supports it too."""
    return True


def is_float16_supported(device=None) -> bool:
    """fp16 lowers on every XLA backend this build targets; bf16 is still
    preferred on TPU — wider exponent, no loss scaling for most models."""
    return True
