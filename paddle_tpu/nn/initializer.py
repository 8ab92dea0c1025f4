"""Parameter initializers (reference: python/paddle/nn/initializer/*).

Each initializer is a callable ``init(key, shape, dtype) -> jax.Array`` — the
idiomatic JAX signature — wrapped in a tiny class for paddle-shaped API parity
(``nn.initializer.XavierUniform()`` etc.).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _fans(shape):
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels are OIHW (paddle convention, see nn/layers.py Conv2D):
    # fan_in = in_ch * receptive field, fan_out = out_ch * receptive field.
    receptive = math.prod(shape[2:])
    return shape[1] * receptive, shape[0] * receptive


class Initializer:
    def __call__(self, key, shape, dtype=jnp.float32):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, key, shape, dtype=jnp.float32):
        return jnp.full(shape, self.value, dtype=dtype)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype=jnp.float32,
                                  minval=self.low, maxval=self.high).astype(dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, key, shape, dtype=jnp.float32):
        x = self.mean + self.std * jax.random.normal(key, shape, dtype=jnp.float32)
        return x.astype(dtype)


class NormalInDtype(Initializer):
    """``Normal(0, std)`` drawn and cast in ONE jitted program, a tensor
    at a time, so a float32 copy of the tensor never sits on the device
    beside it (ISSUE 28: a 5-billion-parameter model served in bf16 has
    no room for its float32 self)."""

    def __init__(self, std=1.0):
        self.std = std

    def __call__(self, key, shape, dtype=jnp.float32):
        return _normal_in_dtype(key, self.std, tuple(shape), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal_in_dtype(key, std, shape, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, key, shape, dtype=jnp.float32):
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype=jnp.float32)
        return (self.mean + self.std * x).astype(dtype)


class XavierUniform(Initializer):
    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, fan_out = _fans(shape)
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(key, shape, jnp.float32, -limit, limit).astype(dtype)


class XavierNormal(Initializer):
    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, fan_out = _fans(shape)
        std = math.sqrt(2.0 / (fan_in + fan_out))
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


class KaimingUniform(Initializer):
    def __init__(self, negative_slope=0.0):
        self.a = negative_slope

    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, _ = _fans(shape)
        gain = math.sqrt(2.0 / (1 + self.a ** 2))
        limit = gain * math.sqrt(3.0 / fan_in)
        return jax.random.uniform(key, shape, jnp.float32, -limit, limit).astype(dtype)


class KaimingNormal(Initializer):
    def __init__(self, negative_slope=0.0):
        self.a = negative_slope

    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, _ = _fans(shape)
        gain = math.sqrt(2.0 / (1 + self.a ** 2))
        std = gain / math.sqrt(fan_in)
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# paddle-style aliases
constant = Constant
uniform = Uniform
normal = Normal


class Assign(Initializer):
    """Initialize from an explicit array (reference initializer/assign.py)."""

    def __init__(self, value):
        self.value = value

    def __call__(self, key, shape, dtype=jnp.float32):
        v = jnp.asarray(self.value, dtype)
        if tuple(v.shape) != tuple(shape):
            raise ValueError(f"Assign value shape {v.shape} != {shape}")
        return v


class Dirac(Initializer):
    """Identity-preserving conv init (reference initializer/dirac.py):
    out[i, i % in, center...] = 1 within each of ``groups`` blocks."""

    def __init__(self, groups: int = 1):
        self.groups = groups

    def __call__(self, key, shape, dtype=jnp.float32):
        if len(shape) < 3:
            raise ValueError("Dirac needs a conv-shaped (O, I, *k) weight")
        out_ch, in_ch = shape[0], shape[1]
        if out_ch % self.groups:
            raise ValueError("out_channels must divide by groups")
        w = np.zeros(shape, np.float32)
        center = tuple(k // 2 for k in shape[2:])
        per_group = out_ch // self.groups
        for g in range(self.groups):
            for i in range(min(per_group, in_ch)):
                w[(g * per_group + i, i) + center] = 1.0
        return jnp.asarray(w, dtype)


class Orthogonal(Initializer):
    """(Semi-)orthogonal matrix init via QR (reference
    initializer/orthogonal.py); tensors are flattened to 2-D."""

    def __init__(self, gain: float = 1.0):
        self.gain = gain

    def __call__(self, key, shape, dtype=jnp.float32):
        if len(shape) < 2:
            raise ValueError("Orthogonal needs >= 2 dims")
        rows = shape[0]
        cols = 1
        for s in shape[1:]:
            cols *= s
        n, m = max(rows, cols), min(rows, cols)
        a = jax.random.normal(key, (n, m), jnp.float32)
        q, r = jnp.linalg.qr(a)
        q = q * jnp.sign(jnp.diagonal(r))     # unique decomposition
        q = q.T if rows < cols else q
        return (self.gain * q.reshape(shape)).astype(dtype)


class ParamAttr:
    """Parameter attribute bundle (reference: python/paddle/fluid/param_attr.py
    ``ParamAttr`` — name/initializer/trainable; regularizer and lr are handled
    by the optimizer's apply_decay_param_fun / LRScheduler on TPU)."""

    def __init__(self, name=None, initializer=None, trainable=True,
                 learning_rate=1.0, regularizer=None, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.trainable = trainable
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.need_clip = need_clip


def calculate_gain(nonlinearity: str, param=None) -> float:
    """Recommended init gain per nonlinearity (reference
    initializer.calculate_gain)."""
    import math
    gains = {
        "sigmoid": 1.0, "linear": 1.0, "conv1d": 1.0, "conv2d": 1.0,
        "conv3d": 1.0, "conv1d_transpose": 1.0, "conv2d_transpose": 1.0,
        "conv3d_transpose": 1.0, "tanh": 5.0 / 3.0,
        "relu": math.sqrt(2.0), "selu": 3.0 / 4.0,
    }
    if nonlinearity == "leaky_relu":
        slope = 0.01 if param is None else float(param)
        return math.sqrt(2.0 / (1 + slope ** 2))
    if nonlinearity in gains:
        return gains[nonlinearity]
    raise ValueError(f"unknown nonlinearity {nonlinearity!r}")


class Bilinear(Initializer):
    """Bilinear-upsampling kernel init for transposed convs (reference
    initializer/Bilinear): weight (C_in, C_out, k, k) gets the classic
    interpolation stencil per channel pair's diagonal."""

    def __call__(self, key, shape, dtype=jnp.float32):
        from ..framework.errors import enforce
        enforce(len(shape) == 4, "Bilinear init expects a 4-D conv weight")
        k = shape[-1]
        enforce(shape[-2] == k, "Bilinear init expects square kernels")
        f = (k + 1) // 2
        c = f - 1 if k % 2 == 1 else f - 0.5
        og = np.ogrid[:k, :k]
        filt = ((1 - np.abs(og[0] - c) / f)
                * (1 - np.abs(og[1] - c) / f)).astype(np.float32)
        w = np.broadcast_to(filt, shape).copy()
        return jnp.asarray(w, dtype)


_global_initializer = {"weight": None, "bias": None}


def set_global_initializer(weight_init, bias_init=None):
    """Reference set_global_initializer: default initializers used by
    Layer.create_parameter when no per-parameter initializer is given.
    Pass (None, None) to reset."""
    _global_initializer["weight"] = weight_init
    _global_initializer["bias"] = bias_init
