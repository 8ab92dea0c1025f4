"""paddle.inference parity (E1): the deployment-facing predictor facade.

Reference: AnalysisPredictor (inference/api/analysis_predictor.h:90) — load
a saved program + params, run an optimization pass pipeline, execute with
zero-copy IO; python surface ``paddle.inference.Config`` /
``create_predictor`` / ``predictor.run``.

TPU-native: the saved artifact is jit-exported StableHLO
(paddle_tpu.jit.save); "the pass pipeline" is XLA compiling that module for
the attached device — there is no separate inference executor to build.
This facade keeps the reference's call shapes so serving code ports
directly.

ISSUE 6 grows this package into a real serving subsystem for decoder
models: :mod:`.engine` (ServingEngine: continuous batching over a paged
KV cache), :mod:`.kv_cache` (block allocator + page arrays),
:mod:`.paged_attention` (ragged decode kernel + lax fallback),
:mod:`.latent_attention` (the same over latent pages, absorbed form),
:mod:`.gqa_attention` (grouped-query heads, a window and a sink),
:mod:`.ssm_state` (a Mamba-2 layer's state a sequence: the decode kernel
and the chunked scan),
:mod:`.scheduler` (admission/preemption policy).  The legacy Config
routes onto it via ``enable_continuous_batching`` +
``set_decoder_model`` — see docs/ARCHITECTURE.md "Serving"."""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import numpy as np

import jax

from .. import jit as pt_jit
from ..framework.errors import enforce

__all__ = ["Config", "Predictor", "create_predictor"]


class Config:
    """≙ paddle.inference.Config(model_dir)."""

    def __init__(self, model_dir: Optional[str] = None):
        self._model_dir = model_dir
        self._device = "tpu"
        self._cb_enabled = False
        self._cb_max_seqs: Optional[int] = None
        self._cb_kv_block_size: Optional[int] = None
        self._decoder_model = None
        self._max_new_tokens = 32
        self._eos_token_id: Optional[int] = None
        self._pad_token_id: Optional[int] = None

    def set_model(self, model_dir: str) -> None:
        self._model_dir = model_dir

    def model_dir(self) -> str:
        return self._model_dir

    def disable_gpu(self) -> None:  # source-compat no-op
        self._device = "cpu"

    def enable_memory_optim(self) -> None:  # XLA owns buffer reuse
        pass

    def switch_ir_optim(self, _=True) -> None:  # XLA owns the pass pipeline
        pass

    # -- serving-engine routing (ISSUE 6) ---------------------------------
    def enable_continuous_batching(self, max_seqs: Optional[int] = None,
                                   kv_block_size: Optional[int] = None
                                   ) -> None:
        """Route this config's predictor onto the paged-KV
        :class:`~paddle_tpu.inference.engine.ServingEngine` (decoder
        models only — attach one with :meth:`set_decoder_model`).  The
        reference predictor call shapes (input handles / ``run()`` /
        output handles) keep working; under the hood each batch row
        becomes a ragged engine request."""
        self._cb_enabled = True
        self._cb_max_seqs = max_seqs
        self._cb_kv_block_size = kv_block_size

    def continuous_batching_enabled(self) -> bool:
        return self._cb_enabled

    def set_decoder_model(self, model, max_new_tokens: int = 32,
                          eos_token_id: Optional[int] = None,
                          pad_token_id: Optional[int] = None) -> None:
        """Attach a decoder model object (``GPTForCausalLM``-like) for
        the continuous-batching path.  A jit-exported StableHLO module
        (``set_model``) cannot decode incrementally — the engine needs
        the live layer to thread paged caches through."""
        self._decoder_model = model
        self._max_new_tokens = int(max_new_tokens)
        self._eos_token_id = eos_token_id
        self._pad_token_id = pad_token_id


class Predictor:
    """≙ AnalysisPredictor's python surface: named input handles, run(),
    named output fetch."""

    def __init__(self, config: Config):
        enforce(config.model_dir(), "Config.set_model(path) first")
        self._layer = pt_jit.load(config.model_dir())
        n_in = len(self._layer.input_spec)
        self._input_names = [
            s.name or f"input_{i}"
            for i, s in enumerate(self._layer.input_spec)]
        self._inputs: Dict[str, Any] = {}
        self._outputs: List[Any] = []
        assert len(self._input_names) == n_in

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name: str) -> "_Handle":
        return _Handle(self._inputs, name)

    def run(self) -> None:
        args = [self._inputs[n] for n in self._input_names]
        out = self._layer(*args)
        self._outputs = list(out) if isinstance(out, (tuple, list)) else [out]

    def get_output_names(self) -> List[str]:
        return [f"output_{i}" for i in range(len(self._outputs))]

    def get_output_handle(self, name: str) -> "_OutHandle":
        idx = int(name.split("_")[-1])
        return _OutHandle(self._outputs, idx)


class _Handle:
    def __init__(self, store: Dict[str, Any], name: str):
        self._store, self._name = store, name

    def copy_from_cpu(self, arr) -> None:
        self._store[self._name] = np.asarray(arr)

    def reshape(self, shape) -> None:  # source-compat no-op (static shapes)
        pass


class _OutHandle:
    def __init__(self, outputs: List[Any], idx: int):
        self._outputs, self._idx = outputs, idx

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._outputs[self._idx])


class EnginePredictor:
    """Reference predictor call shapes over the serving engine: a batch
    ``run()`` submits every row as a ragged request (trailing pad
    stripped), drives the engine to completion, and pads the generated
    continuations back into one ``(batch, max_len)`` output tensor."""

    def __init__(self, config: Config):
        enforce(config._decoder_model is not None,
                "enable_continuous_batching needs set_decoder_model(model)"
                " — an exported StableHLO module cannot decode "
                "incrementally")
        from .engine import ServingEngine
        self._config = config
        self.engine = ServingEngine(config._decoder_model,
                                    max_seqs=config._cb_max_seqs,
                                    kv_block_size=config._cb_kv_block_size)
        self._input_names = ["input_ids"]
        self._inputs: Dict[str, Any] = {}
        self._outputs: List[Any] = []

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name: str) -> "_Handle":
        return _Handle(self._inputs, name)

    def run(self) -> None:
        cfg = self._config
        ids = np.asarray(self._inputs["input_ids"])
        enforce(ids.ndim == 2, f"input_ids must be (batch, len), "
                f"got {ids.shape}")
        prompts = []
        for row in ids:
            toks = [int(t) for t in row]
            if cfg._pad_token_id is not None:
                while toks and toks[-1] == cfg._pad_token_id:
                    toks.pop()
            prompts.append(toks)
        outs = self.engine.generate(prompts,
                                    max_new_tokens=cfg._max_new_tokens,
                                    eos_token_id=cfg._eos_token_id)
        full = [p + o for p, o in zip(prompts, outs)]
        width = max(len(f) for f in full)
        pad = cfg._pad_token_id if cfg._pad_token_id is not None else 0
        out = np.full((len(full), width), pad, np.int64)
        for i, f in enumerate(full):
            out[i, :len(f)] = f
        self._outputs = [out]

    def get_output_names(self) -> List[str]:
        return [f"output_{i}" for i in range(len(self._outputs))]

    def get_output_handle(self, name: str) -> "_OutHandle":
        return _OutHandle(self._outputs, int(name.split("_")[-1]))


def create_predictor(config: Config):
    if config.continuous_batching_enabled():
        return EnginePredictor(config)
    return Predictor(config)


# reference paddle.inference __all__ parity: type enums + utility surface
import enum as _enum

import numpy as _np


class DataType(_enum.Enum):
    FLOAT32 = "float32"
    FLOAT16 = "float16"
    INT8 = "int8"
    INT32 = "int32"
    INT64 = "int64"
    UINT8 = "uint8"
    BOOL = "bool"


class PlaceType(_enum.Enum):
    CPU = "cpu"
    GPU = "gpu"        # maps to the accelerator (TPU) on this stack
    XPU = "xpu"
    UNK = "unk"


class PrecisionType(_enum.Enum):
    Float32 = "float32"
    Half = "float16"
    Int8 = "int8"


Tensor = _Handle      # the predictor's tensor handle role


def get_version() -> str:
    from .. import __version__
    return __version__


def get_trt_compile_version():
    return (0, 0, 0)       # TensorRT is N/A on TPU (XLA is the engine)


def get_trt_runtime_version():
    return (0, 0, 0)


def get_num_bytes_of_data_type(dtype) -> int:
    name = dtype.value if isinstance(dtype, DataType) else str(dtype)
    return _np.dtype(name).itemsize


class PredictorPool:
    """Reference PredictorPool(config, size): N independent predictors —
    here they share the compiled XLA executable (compilation is cached),
    so the pool is a list of Predictor facades."""

    def __init__(self, config: Config, size: int = 1):
        self._predictors = [create_predictor(config) for _ in range(size)]

    def retrive(self, idx: int) -> Predictor:   # reference spelling
        return self._predictors[idx]

    retrieve = retrive


__all__ += ["DataType", "PlaceType", "PrecisionType", "Tensor",
            "get_version", "get_trt_compile_version",
            "get_trt_runtime_version", "get_num_bytes_of_data_type",
            "PredictorPool"]


# -- the serving subsystem (ISSUE 6) ----------------------------------------
from .engine import CollectTimeout, ServingEngine  # noqa: E402
from .gqa_attention import gqa_decode  # noqa: E402
from .kv_cache import (BlockAllocator, PagedKVCache,  # noqa: E402
                       StateLayer, WindowLayer)
from .latent_attention import latent_attention  # noqa: E402
from .paged_attention import paged_attention  # noqa: E402
from .scheduler import ContinuousBatchingScheduler  # noqa: E402
from .ssm_state import ssm_chunk_scan, ssm_state_decode  # noqa: E402

__all__ += ["ServingEngine", "CollectTimeout", "PagedKVCache",
            "BlockAllocator", "ContinuousBatchingScheduler",
            "paged_attention", "latent_attention", "EnginePredictor",
            "WindowLayer", "gqa_decode", "StateLayer", "ssm_state_decode",
            "ssm_chunk_scan"]

# -- the serving fleet (ISSUE 16) -------------------------------------------
from . import fleet  # noqa: E402

__all__ += ["fleet"]
