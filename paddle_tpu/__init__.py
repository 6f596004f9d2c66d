"""paddle_tpu — a TPU-native deep learning framework.

A ground-up rebuild of the PaddlePaddle capability surface (reference mounted at
/root/reference, see SURVEY.md) in idiomatic JAX/XLA/pallas/pjit:

- ``Tensor`` wraps ``jax.Array``; eager ("dygraph") ops are jnp compositions
  recorded on a per-op ``jax.vjp`` tape so ``loss.backward()`` works.
- ``jit.to_static`` replaces ProgramDesc + Executor: trace once, XLA compiles;
  under jit the tape is bypassed and ``jax.grad`` differentiates.
- ``distributed`` maps fleet/collective semantics onto named mesh axes with
  ``shard_map``/pjit and XLA collectives over ICI/DCN.
"""
from . import core  # noqa: F401
from . import tensor  # noqa: F401
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_flags,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    seed,
    set_default_dtype,
    set_device,
    set_flags,
    uint8,
)
from .core.random import get_cuda_rng_state, get_rng_state, set_cuda_rng_state, set_rng_state  # noqa: F401
from .framework import Tensor  # noqa: F401
from .framework.engine import backward, enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401
from .tensor import *  # noqa: F401,F403
from . import autograd  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import jit  # noqa: F401
from . import amp  # noqa: F401
from . import distributed  # noqa: F401
from . import hapi  # noqa: F401
from . import incubate  # noqa: F401
from . import io  # noqa: F401
from . import distribution  # noqa: F401
from . import inference  # noqa: F401
from . import metric  # noqa: F401
from . import onnx  # noqa: F401
from . import profiler  # noqa: F401
from . import serving  # noqa: F401
from . import quantization  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import vision  # noqa: F401
from . import compat  # noqa: F401
from . import dataset  # noqa: F401
from . import device  # noqa: F401
from . import hub  # noqa: F401
from . import reader  # noqa: F401
from . import sysconfig  # noqa: F401
from .hapi import Model  # noqa: F401
from .hapi import flops, summary  # noqa: F401
from . import utils  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .framework.io import load, save  # noqa: F401
from .framework.tensor import Parameter  # noqa: F401
from .nn.layer.layers import ParamAttr  # noqa: F401
from .version import __version__  # noqa: F401


_static_mode = False


def disable_static(*a, **k):
    """Return to dygraph (the default mode)."""
    global _static_mode
    _static_mode = False


def enable_static(*a, **k):
    """Enter static-graph compat mode: ``paddle.static.data`` placeholders
    + ops on them build a deferred-jax Program executed by
    ``paddle.static.Executor`` (optionally whole-program-jitted via
    ``CompiledProgram``).  Graph building works on static Variables in
    either mode; this flag exists for reference-code parity and
    ``in_dynamic_mode`` reporting."""
    global _static_mode
    _static_mode = True


import builtins as _builtins  # noqa: E402

def in_dynamic_mode() -> _builtins.bool:
    from .core.flags import flag as _flag

    # _builtins.bool: the module-level `bool = bool_` dtype alias below
    # shadows the builtin for every function defined in this module
    return _builtins.bool(_flag("FLAGS_eager_mode")) and not _static_mode

from .core.device import CUDAPinnedPlace, NPUPlace  # noqa: E402,F401
from .core import dtype as _dtype_mod  # noqa: E402
import numpy as _np_mod  # noqa: E402
# paddle.bool / paddle.dtype (data_type.py parity aliases): paddle.dtype is
# the dtype *type* — np.dtype gives isinstance checks + dtype('float32')
bool = _dtype_mod.bool_  # noqa: A001
dtype = _np_mod.dtype


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """paddle.set_printoptions parity (delegates to numpy's print options,
    which .numpy()/repr paths use)."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not _builtins.bool(sci_mode)
    _np.set_printoptions(**kw)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """paddle.create_parameter parity (fluid layers.create_parameter)."""
    from .nn.layer.layers import Layer

    helper = Layer()
    p = helper.create_parameter(shape, attr=attr, dtype=dtype,
                                is_bias=is_bias,
                                default_initializer=default_initializer)
    if name:
        p.name = name
    return p


def batch(reader, batch_size, drop_last=False):
    """paddle.batch parity: wrap an instance reader into a batch reader."""
    def batch_reader():
        buf = []
        for instance in reader():
            buf.append(instance)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batch_reader


def check_shape(shape, op_name="", expected_shape_type=(list, tuple),
                expected_element_type=(int,), expected_tensor_dtype=("int32", "int64")):
    """data_feeder.py:142 parity: validate a shape argument's types."""
    from .core.errors import InvalidArgumentError

    if not isinstance(shape, expected_shape_type):
        raise InvalidArgumentError(
            "%s: shape must be %s, got %r" % (op_name, expected_shape_type,
                                              type(shape)))
    for item in shape:
        if not isinstance(item, expected_element_type):
            raise InvalidArgumentError(
                "%s: shape element must be %s, got %r"
                % (op_name, expected_element_type, type(item)))
