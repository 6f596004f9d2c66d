"""``paddle_tpu.inference`` — the deployment predictor.

Reference parity: ``python/paddle/inference/__init__.py`` surface over
``paddle/fluid/inference/api/`` — ``Config`` (analysis_config.cc),
``create_predictor``/``Predictor`` (``analysis_predictor.cc:145`` create,
``:889`` Run), handle-based IO (``GetInputNames``/``GetInputHandle``/
``copy_from_cpu``/``Run``/``copy_to_cpu``), ``PredictorPool``.

TPU-native design: the "analysis" pipeline (IR passes, TRT/MKLDNN engines,
memory-optim pass) dissolves — the artifact IS a compiled-ready StableHLO
program (``jit.save``), and XLA applies the graph optimizations at load
time.  A handle's ``copy_from_cpu`` is an async ``jax.device_put`` (the
zero-copy staging analog); ``Run`` executes the loaded executable;
``copy_to_cpu`` blocks on the result.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import jax
import numpy as np

from ..core.errors import InvalidArgumentError

__all__ = ["Config", "Predictor", "PredictorTensor", "Tensor",
           "create_predictor", "PredictorPool", "get_version",
           "DataType", "PlaceType", "PrecisionType",
           "get_num_bytes_of_data_type",
           "GenerationPool", "create_generation_pool",
           "kv_reachable_bytes", "DuplicateRequestError",
           "SpeculativePool", "BlockDiffusionPool"]


class DataType:
    FLOAT32 = "float32"
    FLOAT16 = "float16"
    BFLOAT16 = "bfloat16"
    INT32 = "int32"
    INT64 = "int64"
    UINT8 = "uint8"
    INT8 = "int8"
    BOOL = "bool"


class PlaceType:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    XPU = "xpu"


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


def get_num_bytes_of_data_type(dtype) -> int:
    return np.dtype(dtype).itemsize


def get_version() -> str:
    from ..version import __version__

    return "paddle_tpu inference %s" % __version__


class Config:
    """analysis_config.cc parity (the knobs with TPU meaning act; GPU/TRT/
    MKLDNN toggles are stored and reported, their work being XLA's)."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        # paddle convention: Config(model_dir) or Config(prog, params);
        # here one artifact prefix covers both files (jit.save layout)
        self._model_prefix = prog_file
        self._params_file = params_file
        self._device = "tpu" if any(
            d.platform == "tpu" for d in jax.devices()) else "cpu"
        self._enable_memory_optim = True
        self._switch_ir_optim = True  # XLA always optimizes; informational

    def set_model(self, prog_file: str, params_file: Optional[str] = None):
        self._model_prefix = prog_file
        self._params_file = params_file

    def model_dir(self) -> Optional[str]:
        return self._model_prefix

    def prog_file(self) -> Optional[str]:
        return self._model_prefix

    def enable_use_gpu(self, memory_pool_init_size_mb: int = 100,
                       device_id: int = 0):
        self._device = "gpu"

    def disable_gpu(self):
        self._device = "cpu"

    def enable_xpu(self, *a, **k):
        self._device = "tpu"

    def use_gpu(self) -> bool:
        return self._device == "gpu"

    def enable_memory_optim(self):
        self._enable_memory_optim = True

    def switch_ir_optim(self, x: bool = True):
        """Accepted for parity; XLA always optimizes at compile. The
        reference's const-fold/conv-bn-fuse ir passes have a save-time
        analog here: export with ``jit.save(..., params_const=True)`` so
        weights are program constants XLA can fold through."""
        self._switch_ir_optim = x

    def set_cpu_math_library_num_threads(self, n: int):
        self._cpu_threads = n

    def summary(self) -> str:
        return "Config(model=%r, device=%s)" % (self._model_prefix, self._device)


class PredictorTensor:
    """The IO handle (paddle_infer::Tensor parity): staged host↔device."""

    def __init__(self, name: str):
        self.name = name
        self._value = None

    def reshape(self, shape):
        pass  # shapes come from the artifact; kept for API parity

    def copy_from_cpu(self, data: np.ndarray) -> None:
        self._value = jax.device_put(np.asarray(data))  # async staging

    def share_external_data(self, data) -> None:
        self._value = data if isinstance(data, jax.Array) else jax.device_put(data)

    def copy_to_cpu(self) -> np.ndarray:
        if self._value is None:
            raise InvalidArgumentError("output %r not computed yet; Run() first"
                                       % self.name)
        return np.asarray(self._value)  # blocks on the async result

    def shape(self):
        return list(self._value.shape) if self._value is not None else None

    def type(self):
        return str(self._value.dtype) if self._value is not None else None


Tensor = PredictorTensor  # paddle_infer.Tensor alias


class Predictor:
    """analysis_predictor.cc:145/:889 parity over a jit.save artifact."""

    def __init__(self, config: Config):
        from ..jit import load as jit_load

        if config.model_dir() is None:
            raise InvalidArgumentError("Config has no model set")
        prefix = config.model_dir()
        if not os.path.exists(prefix + ".pdmodel.json"):
            raise InvalidArgumentError(
                "no artifact at %r (expected jit.save output: "
                "<prefix>.pdmodel.stablehlo + .pdiparams.npz + .pdmodel.json)"
                % prefix)
        self._layer = jit_load(prefix)
        n_in = self._layer._meta.get("n_inputs", 1)
        self._input_names = ["input_%d" % i for i in range(n_in)]
        self._inputs = {n: PredictorTensor(n) for n in self._input_names}
        self._output_names: List[str] = []
        self._outputs: Dict[str, PredictorTensor] = {}

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name: str) -> PredictorTensor:
        if name not in self._inputs:
            raise InvalidArgumentError("unknown input %r (have %s)"
                                       % (name, self._input_names))
        return self._inputs[name]

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """New-style ``predictor.run([arrays])`` or handle-style ``Run()``."""
        if inputs is not None:
            for n, a in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(np.asarray(a))
        missing = [n for n in self._input_names if self._inputs[n]._value is None]
        if missing:
            raise InvalidArgumentError(
                "inputs %s not set; copy_from_cpu first" % missing)
        args = [self._inputs[n]._value for n in self._input_names]
        out = self._layer(*args)
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                lambda t: t.value if hasattr(t, "value") else t, out,
                is_leaf=lambda t: hasattr(t, "value")))
        self._output_names = ["output_%d" % i for i in range(len(leaves))]
        self._outputs = {}
        for n, v in zip(self._output_names, leaves):
            h = PredictorTensor(n)
            h._value = v
            self._outputs[n] = h
        if inputs is not None:
            return [np.asarray(v._value) for v in self._outputs.values()]
        return True

    Run = run  # C++-style casing parity

    def get_output_names(self) -> List[str]:
        if not self._output_names:
            # run once lazily not possible without inputs; expose canonical
            return ["output_0"]
        return list(self._output_names)

    def get_output_handle(self, name: str) -> PredictorTensor:
        if name not in self._outputs:
            raise InvalidArgumentError(
                "output %r not available; call run() first" % name)
        return self._outputs[name]

    def try_shrink_memory(self):
        pass  # XLA owns buffers

    def clear_intermediate_tensor(self):
        pass


def create_predictor(config: Config) -> Predictor:
    """paddle_infer.create_predictor parity."""
    return Predictor(config)


class PredictorPool:
    """paddle_inference_api.h:183 parity: N predictors sharing one artifact."""

    def __init__(self, config: Config, size: int = 1):
        self._predictors = [Predictor(config) for _ in range(max(1, size))]

    def retrieve(self, idx: int) -> Predictor:
        if not (0 <= idx < len(self._predictors)):
            raise InvalidArgumentError(
                "PredictorPool index %d out of range [0, %d)"
                % (idx, len(self._predictors)))
        return self._predictors[idx]


# -- the serving engine: KV-cached continuous-batching generation ----------
# The artifact Predictor above runs a FIXED exported program; generation
# needs the cache-threaded forward of a live model, so the pool owns the
# model (docs/DESIGN.md "prefill/decode split").
from .generation import (  # noqa: E402,F401
    DuplicateRequestError, GenerationPool, kv_reachable_bytes)
from .speculative import SpeculativePool  # noqa: E402,F401
from .block_diffusion import BlockDiffusionPool  # noqa: E402,F401


def create_generation_pool(model, max_len: int, **kwargs) -> GenerationPool:
    """Build a :class:`GenerationPool` over a live cached-decode model
    (``models.TransformerLM``): slot-based continuous batching, one
    batched decode step per tick, bucketed prefill — the serving analog
    of ``create_predictor`` for autoregressive generation."""
    return GenerationPool(model, max_len, **kwargs)
