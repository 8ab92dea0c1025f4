"""paddle.jit parity: save/load a trained model for inference (E1/E5).

Reference surfaces being matched:
- ``paddle.jit.to_static`` / ``ProgramTranslator`` (dy2static AST rewrite,
  program_translator.py:236) — on TPU ``jax.jit`` traces python directly,
  so ``to_static`` is a thin alias that exists for ported code;
- ``paddle.jit.save`` → inference model (fluid/io.py save_inference_model):
  here the forward is exported as serialized StableHLO via ``jax.export``
  (compiler-level, versioned, loadable without the model class) together
  with the parameters;
- loading for serving (AnalysisPredictor's load half, E1) =
  :func:`paddle_tpu.jit.load` → a callable ``TranslatedLayer`` analog.

The saved artifact is a directory:
  ``model.stablehlo``  — jax.export serialization of apply(params, *inputs)
  ``params/``          — sharded checkpoint (distributed.checkpoint format)
  ``meta.json``        — input specs / structure
"""
from __future__ import annotations

import json
import os
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import export as jax_export

from .distributed.checkpoint import load_sharded, save_sharded
from .framework.errors import enforce
from .utils import fsio

__all__ = ["to_static", "save", "load", "InputSpec", "TranslatedLayer"]


class InputSpec:
    """≙ paddle.static.InputSpec(shape, dtype, name)."""

    def __init__(self, shape: Sequence[Optional[int]], dtype="float32",
                 name: Optional[str] = None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def sds(self, scope=None, prefix: str = "d") -> jax.ShapeDtypeStruct:
        """ShapeDtypeStruct; None/-1 dims become jax.export symbolic dims
        (the paddle contract: None = dynamic, typically the batch axis)."""
        dims = []
        for i, d in enumerate(self.shape):
            if d is None or (isinstance(d, int) and d < 0):
                (sym,) = jax_export.symbolic_shape(f"{prefix}{i}",
                                                   scope=scope)
                dims.append(sym)
            else:
                dims.append(int(d))
        return jax.ShapeDtypeStruct(tuple(dims), jnp.dtype(self.dtype))

    def to_json(self):
        return {"shape": list(self.shape), "dtype": str(self.dtype),
                "name": self.name}

    @staticmethod
    def from_json(d):
        return InputSpec(d["shape"], d["dtype"], d.get("name"))


def to_static(function=None, input_spec=None, **kw):
    """≙ @paddle.jit.to_static — jax traces python directly, so this is
    jax.jit with the decorator calling conventions preserved.
    ``ProgramTranslator.enable(False)`` routes calls to the raw python
    function (the reference's debug-eagerly workflow)."""
    def deco(fn):
        from .observability.compilation import track_jit
        from .observability.compilecache import enable_persistent_cache
        # disk cache so a warm process re-loads instead of re-compiling
        # these programs
        enable_persistent_cache()
        # every to_static callsite reports compiles/retraces to the run
        # doctor under its own name (ISSUE 4)
        jitted = track_jit(jax.jit(fn),
                           name=f"to_static.{getattr(fn, '__name__', fn)}")
        import functools

        @functools.wraps(fn)
        def dispatch(*args, **kwargs):
            if not _translator_state["enabled"] or getattr(
                    fn, "__not_to_static__", False):
                return fn(*args, **kwargs)
            return jitted(*args, **kwargs)
        dispatch.__wrapped_jit__ = jitted
        return dispatch
    if function is None:
        return deco
    return deco(function)


def save(layer, path: str, input_spec: List[InputSpec]) -> None:
    """Export ``layer`` (a Layer with .apply / .eval) for inference.

    The forward is traced at the given specs in eval mode and serialized as
    StableHLO — the artifact needs no python model code to run (the property
    that makes AnalysisPredictor deployments work).
    """
    os.makedirs(path, exist_ok=True)
    layer.eval()
    # plain dict: load_sharded's templateless restore builds plain dicts,
    # and OrderedDict vs dict are different pytree node types to jax.export
    params = dict(layer.state_dict())

    def fwd(p, *inputs):
        return layer.apply(p, *inputs)

    scope = jax_export.SymbolicScope()
    sds = [s.sds(scope=scope, prefix=f"s{i}_")
           for i, s in enumerate(input_spec)]
    exported = jax_export.export(jax.jit(fwd))(params, *sds)
    fsio.write_bytes(os.path.join(path, "model.stablehlo"),
                     bytes(exported.serialize()))
    save_sharded(params, os.path.join(path, "params"))
    fsio.write_bytes(
        os.path.join(path, "meta.json"),
        json.dumps({"input_spec": [s.to_json() for s in input_spec]}
                   ).encode("utf-8"))


class TranslatedLayer:
    """Loaded inference callable (≙ paddle.jit.TranslatedLayer /
    the predictor's run surface)."""

    def __init__(self, path: str):
        with open(os.path.join(path, "model.stablehlo"), "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        self._params = load_sharded(os.path.join(path, "params"))
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self.input_spec = [InputSpec.from_json(d)
                           for d in meta["input_spec"]]
        self._call = jax.jit(self._exported.call)

    def __call__(self, *inputs):
        args = [jnp.asarray(np.asarray(x)) for x in inputs]
        return self._call(self._params, *args)


def load(path: str) -> TranslatedLayer:
    enforce(os.path.isdir(path), f"no exported model at {path!r}")
    return TranslatedLayer(path)


def not_to_static(fn=None):
    """Mark a function to be excluded from to_static conversion (reference
    jit.not_to_static).  One-codepath runtime: tracing is jax's and the
    marker is metadata — the function runs as plain python either way."""
    if fn is None:
        return not_to_static
    fn.__not_to_static__ = True
    return fn


def set_code_level(level: int = 100, also_to_stdout: bool = False):
    """Reference jit.set_code_level: controls dy2static transformed-code
    logging.  There is no source transform here (jax traces python
    directly), so this records the setting for API parity."""
    _translator_state["code_level"] = level


def set_verbosity(level: int = 0, also_to_stdout: bool = False):
    _translator_state["verbosity"] = level


_translator_state = {"enabled": True, "code_level": 0, "verbosity": 0}


class ProgramTranslator:
    """Reference dy2static ProgramTranslator singleton: enable() toggles
    whether @to_static functions are traced (False = run eagerly)."""

    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static: bool = True):
        _translator_state["enabled"] = bool(enable_to_static)

    @property
    def enable_to_static(self):
        return _translator_state["enabled"]


class TracedLayer:
    """Reference jit.TracedLayer (dygraph trace → static program).  The
    jax analog: trace(layer, inputs) jit-compiles the layer's forward and
    records example inputs; ``save_inference_model`` delegates to jit.save
    via the captured InputSpec."""

    def __init__(self, layer, inputs):
        import jax
        self._layer = layer
        self._inputs = inputs
        sd = layer.state_dict()
        self._fn = jax.jit(lambda params, *a: layer.apply(params, *a))
        self._params = sd

    def __call__(self, *inputs):
        return self._fn(self._params, *inputs)

    @staticmethod
    def trace(layer, inputs):
        tl = TracedLayer(layer, inputs)
        return tl(*inputs), tl

    def save_inference_model(self, path: str, feed=None, fetch=None):
        specs = [InputSpec(tuple(jnp.asarray(i).shape),
                           str(jnp.asarray(i).dtype)) for i in self._inputs]
        save(self._layer, path, specs)


__all__ += ["TracedLayer", "ProgramTranslator", "set_code_level",
            "set_verbosity", "not_to_static"]
