"""paddle.Tensor method surface on jax arrays (reference:
python/paddle/tensor/__init__.py monkey_patch_* — the reference installs
~200 methods onto its Tensor; here the paddle-shaped methods are
installed onto ``jaxlib ArrayImpl`` AND ``jax.core.Tracer`` so the same
idioms work eagerly and inside jit traces).

Rules:
- NEVER overrides an attribute the jax types already have (reshape,
  astype, sum, mean, item, ... stay jax's own);
- methods are thin jnp delegates, so tracing semantics are untouched;
- host-only methods (``numpy``, ``cpu``) raise jax's natural
  concretization error under jit, which is the correct failure mode.

``x.stop_gradient = True`` (instance attribute mutation) cannot exist on
immutable arrays — use ``x.detach()`` / ``paddle.no_grad`` instead
(docs/MIGRATION.md: in-place ops).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .dtype import convert_dtype

__all__ = ["install_tensor_methods",
           "install_reference_method_contract",
           "INSTALLED_METHODS"]


def _numpy(self):
    return np.asarray(self)


def _detach(self):
    return jax.lax.stop_gradient(self)


def _cpu(self):
    return jax.device_put(self, jax.devices("cpu")[0])


def _cuda(self, device_id: int = 0):
    return jax.device_put(self, jax.devices()[device_id])


def _delegate(name, kind: str = "pt"):
    """Bind a PACKAGE-LEVEL paddle_tpu (or paddle_tpu.linalg) function as
    a method (single source of truth — the functional op; the reference's
    monkey_patch does exactly this with its op lambdas)."""
    def method(self, *args, **kwargs):
        import paddle_tpu as pt
        mod = pt.linalg if kind == "linalg" else pt
        return getattr(mod, name)(self, *args, **kwargs)
    method.__name__ = name
    return method


def _resolve_targets() -> list:
    """The classes the method surface lands on.  ``ArrayImpl`` lives in
    a jax-private module; a jax refactor that moves it must DEGRADE the
    install (RuntimeWarning, tracer-only surface) — never hard-fail
    ``import paddle_tpu`` (ADVICE round 5)."""
    import warnings
    targets = []
    try:
        from jax._src.array import ArrayImpl
        targets.append(ArrayImpl)
    except ImportError:
        warnings.warn(
            "paddle_tpu: jax._src.array.ArrayImpl not importable under "
            f"jax {jax.__version__} — paddle Tensor methods will be "
            "unavailable on concrete arrays (traced code is unaffected)",
            RuntimeWarning)
    tracer = getattr(jax.core, "Tracer", None)
    if tracer is not None:
        targets.append(tracer)
    else:
        warnings.warn(
            "paddle_tpu: jax.core.Tracer not found — paddle Tensor "
            "methods will be unavailable inside jit traces",
            RuntimeWarning)
    return targets


def _install(table) -> None:
    """Shared install loop: bind onto the concrete array class and the
    tracer base, never touching existing attributes; sealed-type
    failures are LOUD (a silent skip would vanish the whole surface)."""
    targets = _resolve_targets()
    failed = []
    for name, fn in table.items():
        for t in targets:
            if not hasattr(t, name):
                try:
                    setattr(t, name, fn)
                except (AttributeError, TypeError):
                    failed.append((t.__name__, name))
                    continue
                if name not in INSTALLED_METHODS:
                    INSTALLED_METHODS.append(name)
    if failed:
        import warnings
        warnings.warn(
            f"tensor-method install skipped {len(failed)} bindings "
            f"(sealed type?): {failed[:5]}...", RuntimeWarning)


def _dim(self):
    return self.ndim


def _binary(fn):
    return lambda self, y: fn(self, y)


def _unary(fn):
    return lambda self: fn(self)


_METHODS = {
    "numpy": _numpy,
    "detach": _detach,
    "cpu": _cpu,
    "cuda": _cuda,
    "cast": _delegate("cast"),
    "unsqueeze": _delegate("unsqueeze"),
    "norm": _delegate("norm"),
    "numel": _delegate("numel"),
    "dim": _dim,
    "ndimension": _dim,
    "t": _delegate("t"),
    "expand": _delegate("expand"),
    "tile": _delegate("tile"),
    "gather": _delegate("gather"),
    "allclose": _delegate("allclose"),
    # binary ops (paddle spelling)
    "add": _binary(jnp.add),
    "subtract": _binary(jnp.subtract),
    "multiply": _binary(jnp.multiply),
    "divide": _binary(jnp.divide),
    "matmul": _binary(jnp.matmul),
    "mm": _binary(jnp.matmul),
    "mod": _binary(jnp.mod),
    "pow": _binary(jnp.power),
    "maximum": _binary(jnp.maximum),
    "minimum": _binary(jnp.minimum),
    "equal": _binary(jnp.equal),
    "not_equal": _binary(jnp.not_equal),
    "greater_than": _binary(jnp.greater),
    "greater_equal": _binary(jnp.greater_equal),
    "less_than": _binary(jnp.less),
    "less_equal": _binary(jnp.less_equal),
    "logical_and": _binary(jnp.logical_and),
    "logical_or": _binary(jnp.logical_or),
    # unary math (paddle spelling)
    "abs": _unary(jnp.abs),
    "exp": _unary(jnp.exp),
    "log": _unary(jnp.log),
    "sqrt": _unary(jnp.sqrt),
    "rsqrt": _unary(jax.lax.rsqrt),
    "square": _unary(jnp.square),
    "tanh": _unary(jnp.tanh),
    "sigmoid": _unary(jax.nn.sigmoid),
    "floor": _unary(jnp.floor),
    "ceil": _unary(jnp.ceil),
    "sign": _unary(jnp.sign),
    "erf": _unary(jax.scipy.special.erf),
    "isnan": _unary(jnp.isnan),
    "isinf": _unary(jnp.isinf),
    "isfinite": _unary(jnp.isfinite),
}

INSTALLED_METHODS: list = []


def install_tensor_methods() -> None:
    """Install the method table onto the concrete array class and the
    tracer base; existing attributes are never touched.  The class is
    imported, NOT derived from a live array — materializing one here
    would initialize the backend at package-import time, and a process
    that has touched the backend holds the chip."""
    _install(_METHODS)


# The reference Tensor method contract (python/paddle/tensor/__init__.py
# ``tensor_method_func`` — the exact list the reference monkey-patches
# onto its Tensor).  Everything here that has a package-level
# counterpart (paddle_tpu.<name>, paddle_tpu.linalg.<name>, or the
# non-inplace base of a ``name_``) is auto-delegated as a method, with
# ``self`` as the first argument — byte-for-byte the reference's own
# binding rule.
_REF_TENSOR_METHODS = [
    "matmul",
    "dot",
    "cov",
    "norm",
    "cond",
    "transpose",
    "lstsq",
    "dist",
    "t",
    "cross",
    "cholesky",
    "bmm",
    "histogram",
    "bincount",
    "mv",
    "matrix_power",
    "qr",
    "eigvals",
    "eigvalsh",
    "abs",
    "acos",
    "all",
    "any",
    "asin",
    "atan",
    "ceil",
    "ceil_",
    "cos",
    "cosh",
    "cumsum",
    "cumprod",
    "logit",
    "exp",
    "exp_",
    "floor",
    "floor_",
    "increment",
    "log",
    "log2",
    "log10",
    "logsumexp",
    "multiplex",
    "pow",
    "prod",
    "reciprocal",
    "reciprocal_",
    "round",
    "round_",
    "rsqrt",
    "rsqrt_",
    "scale",
    "scale_",
    "sign",
    "sin",
    "sinh",
    "sqrt",
    "sqrt_",
    "square",
    "stanh",
    "sum",
    "nansum",
    "nanmean",
    "tanh",
    "tanh_",
    "add_n",
    "max",
    "amax",
    "maximum",
    "min",
    "amin",
    "minimum",
    "fmax",
    "fmin",
    "mm",
    "inner",
    "outer",
    "divide",
    "floor_divide",
    "remainder",
    "mod",
    "floor_mod",
    "multiply",
    "add",
    "add_",
    "subtract",
    "subtract_",
    "atan",
    "logsumexp",
    "inverse",
    "log1p",
    "erf",
    "addmm",
    "clip",
    "clip_",
    "trace",
    "kron",
    "kthvalue",
    "isfinite",
    "isinf",
    "isnan",
    "broadcast_shape",
    "conj",
    "neg",
    "lgamma",
    "equal",
    "equal_all",
    "greater_equal",
    "greater_than",
    "is_empty",
    "less_equal",
    "less_than",
    "logical_and",
    "logical_not",
    "logical_or",
    "logical_xor",
    "not_equal",
    "allclose",
    "isclose",
    "is_tensor",
    "cast",
    "concat",
    "expand",
    "broadcast_to",
    "expand_as",
    "flatten",
    "flatten_",
    "gather",
    "gather_nd",
    "reshape",
    "reshape_",
    "reverse",
    "scatter",
    "scatter_",
    "scatter_nd_add",
    "scatter_nd",
    "shard_index",
    "slice",
    "split",
    "chunk",
    "tensordot",
    "squeeze",
    "squeeze_",
    "stack",
    "strided_slice",
    "transpose",
    "unique",
    "unique_consecutive",
    "unsqueeze",
    "unsqueeze_",
    "unstack",
    "flip",
    "rot90",
    "unbind",
    "roll",
    "tile",
    "argmax",
    "argmin",
    "argsort",
    "masked_select",
    "topk",
    "where",
    "index_select",
    "nonzero",
    "sort",
    "index_sample",
    "mean",
    "std",
    "var",
    "numel",
    "median",
    "quantile",
    "is_complex",
    "is_integer",
    "rank",
    "shape",
    "real",
    "imag",
    "is_floating_point",
    "digamma",
    "diagonal",
    "trunc",
    "bitwise_and",
    "bitwise_or",
    "bitwise_xor",
    "bitwise_not",
    "broadcast_tensors",
    "eig",
    "uniform_",
    "multi_dot",
    "solve",
    "cholesky_solve",
    "triangular_solve",
    "asinh",
    "atanh",
    "acosh",
    "lu",
    "lu_unpack",
    "as_complex",
    "as_real",
    "rad2deg",
    "deg2rad",
    "gcd",
    "lcm",
    "diff",
    "mode",
    "lerp",
    "lerp_",
    "erfinv",
    "erfinv_",
    "angle",
    "moveaxis",
    "repeat_interleave",
    "take_along_axis",
    "put_along_axis",
    "put_along_axis_",
    "exponential_",
]


def _resolve_ref_method(name):
    import paddle_tpu as pt
    fn = getattr(pt, name, None)
    if callable(fn):
        return name, "pt"
    fn = getattr(pt.linalg, name, None)
    if callable(fn):
        return name, "linalg"
    if name.endswith("_"):
        base = name[:-1]
        if callable(getattr(pt, base, None)):
            return base, "pt"
        if callable(getattr(pt.linalg, base, None)):
            return base, "linalg"
    return None, None


# in-place method names (`add_`, `clip_`, ...) delegate to their
# non-mutating bases — immutable arrays can't be written through — so
# `x.add_(y)` computes a NEW array and the receiver is unchanged.
# Ported paddle code calling them for the side effect gets a ONE-TIME
# runtime signal instead of silence (ADVICE round 5).
_INPLACE_WARNED: set = set()


def _warn_inplace(name: str) -> None:
    if name in _INPLACE_WARNED:
        return
    _INPLACE_WARNED.add(name)
    import warnings
    warnings.warn(
        f"paddle_tpu: Tensor.{name}() cannot mutate an immutable jax "
        "array — it returns a new tensor and the receiver is unchanged; "
        "assign the result (docs/MIGRATION.md: in-place ops)",
        UserWarning, stacklevel=3)


def _inplace_delegate(name, base, kind):
    inner = _delegate(base, kind)

    def method(self, *args, **kwargs):
        _warn_inplace(name)
        return inner(self, *args, **kwargs)
    method.__name__ = name
    return method


def _uniform_(self, min=-1.0, max=1.0, seed=0):  # noqa: A002
    """Reference Tensor.uniform_(min, max, seed): a uniform fill of
    SELF's shape/dtype — must NOT fall through to the creation op
    paddle.uniform(shape, ...), whose first argument is a shape.  A
    nonzero ``seed`` is folded into a dedicated key (the reference's
    per-call seeded draw) instead of silently ignored (ADVICE round 5)."""
    _warn_inplace("uniform_")
    if seed:
        key = jax.random.key(int(seed))
        dtype = (self.dtype if jnp.issubdtype(self.dtype, jnp.floating)
                 else jnp.float32)
        return jax.random.uniform(key, self.shape, dtype, min, max)
    import paddle_tpu as pt
    return pt.uniform(self.shape, str(self.dtype), min, max)


# in-place names whose BASE is a creation/op with a non-tensor first
# argument: auto-delegation would be semantically wrong
_REF_METHOD_OVERRIDES = {"uniform_": _uniform_}


def install_reference_method_contract() -> None:
    """Second install pass: the full reference tensor_method_func list,
    auto-delegated.  Runs AFTER the package namespace is fully built
    (end of paddle_tpu/__init__), so every functional op is resolvable."""
    table = dict(_REF_METHOD_OVERRIDES)
    for name in _REF_TENSOR_METHODS:
        if name in table:
            continue
        resolved, kind = _resolve_ref_method(name)
        if resolved is None:
            continue
        if name.endswith("_") and resolved == name[:-1]:
            # `name_` fell through to its non-mutating base: warn once
            # at first call that nothing is mutated
            table[name] = _inplace_delegate(name, resolved, kind)
        else:
            table[name] = _delegate(resolved, kind)
    _install(table)
