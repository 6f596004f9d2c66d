"""Op dispatch: the bridge between the Tensor facade and raw jnp impls.

Reference parity: the generated ``core.ops.*`` fast path + ``Tracer::TraceOp``
(``imperative/tracer.cc:144``): every public op (a) unwraps Tensor arguments,
(b) runs the raw jnp/lax implementation, (c) re-wraps outputs, and (d) when
eager autograd is live, records a :class:`~.engine.GradNode` holding the
``jax.vjp`` pullback — the analog of ``CreateGradOpNode`` (tracer.cc:231).

Three calling conventions coexist:

- **Eager with Tensors** → wrap + (maybe) tape.  This is dygraph mode.
- **Raw arrays / tracers, no Tensors** → passthrough, zero overhead added.
  This is what jitted functional code (``paddle_tpu.jit``) sees.
- **Python scalars/lists only** (creation/random ops) → outputs are wrapped
  Tensors, so the public API is Tensor-in/Tensor-out for eager users.
"""
from __future__ import annotations

import functools
import types
from typing import Any, Callable, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.core import trace_state_clean as _trace_state_clean

from ..core import amp_state
from . import engine
from .tensor import Tensor

_tree = jax.tree_util


def _amp_apply(fn: Callable, op_name: str) -> Callable:
    """Autocast shim (imperative/amp_auto_cast.cc CastedOp analog).

    White-listed ops run in the autocast dtype (MXU-friendly bf16/fp16),
    black-listed ops are forced to float32; everything else runs in the
    dtype it was given.  The cast sits INSIDE the differentiated function,
    so vjp transposes it and gradients return in the caller's dtype.

    A black-listed op may carry ``amp_upcasts_inside``, a predicate over its
    own arguments: where it holds, the op reads its inputs as stored and
    up-casts them inside its reductions, so no float32 copy is made in
    front of it (docs/DESIGN.md 4; ``F.cross_entropy`` on class ids).
    """
    st = amp_state.current()
    if not st.enabled:
        return fn
    if op_name in st.white:
        tgt = jnp.bfloat16 if st.dtype == "bfloat16" else jnp.float16
    elif op_name in st.black:
        tgt = jnp.float32
    else:
        return fn
    inside = op_name in st.black and getattr(fn, "amp_upcasts_inside", None)

    def _cast(v):
        if isinstance(v, (jax.Array, np.ndarray)) \
                and jnp.issubdtype(v.dtype, jnp.floating) and v.dtype != tgt:
            return jnp.asarray(v).astype(tgt)
        return v

    @functools.wraps(fn)
    def casted(*a, **k):
        if inside and inside(*a, **k):
            return fn(*a, **k)
        a = _tree.tree_map(_cast, a)
        k = _tree.tree_map(_cast, k)
        return fn(*a, **k)

    return casted


def _is_leaf(x) -> bool:
    # static-graph Variables are leaves too (one flatten serves both the
    # Tensor path and the symbolic check — see make_op)
    return isinstance(x, Tensor) or (
        _symbolic_cls is not None and isinstance(x, _symbolic_cls))


def _aval(x):
    return (tuple(x.shape), x.dtype)


def _wrap_outputs(out, node=None):
    leaves, treedef = _tree.tree_flatten(out)
    wrapped = []
    k = 0
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            t = Tensor(leaf, stop_gradient=node is None)
            if node is not None:
                t._node = node
                t._leaf_idx = k
            wrapped.append(t)
        else:
            wrapped.append(leaf)
        k += 1
    return _tree.tree_unflatten(treedef, wrapped)


def _is_traced(v) -> bool:
    return isinstance(v, jax.core.Tracer)


def _trace_clean() -> bool:
    """True when no jax trace is ambient (we are in plain eager mode)."""
    return _trace_state_clean()


def _post_op(out_raw, op_name: str, t0) -> None:
    """Eager-path op epilogue: profiling timing (FLAGS_benchmark /
    profiler.start_profiler) and nan/inf scanning (FLAGS_check_nan_inf —
    ``nan_inf_utils_detail`` parity, raising with the op name)."""
    if t0 is not None:
        import time

        from .. import profiler as _prof

        jax.block_until_ready(
            [l for l in _tree.tree_leaves(out_raw) if isinstance(l, jax.Array)])
        _prof.record_op_time(op_name, time.perf_counter() - t0)
    from ..core.flags import flag as _flag

    if _flag("FLAGS_check_nan_inf"):
        for leaf in _tree.tree_leaves(out_raw):
            if isinstance(leaf, jax.Array) and not _is_traced(leaf) \
                    and jnp.issubdtype(leaf.dtype, jnp.inexact):
                if not bool(jnp.isfinite(leaf).all()):
                    from ..core.errors import InvalidArgumentError

                    raise InvalidArgumentError(
                        "nan/inf detected in output of op %r "
                        "(FLAGS_check_nan_inf)" % op_name)


def _maybe_t0():
    from .. import profiler as _prof

    if _prof.is_profiling():
        import time

        return time.perf_counter()
    return None


def make_op(fn: Callable, differentiable: bool = True, op_name: str = "") -> Callable:
    """Wrap a raw-array op into the Tensor-facade calling convention."""
    op_name = op_name or getattr(fn, "__name__", "op")

    @functools.wraps(fn)
    def op(*args, **kwargs):
        run = (_amp_apply(fn, op_name) if amp_state.amp_enabled() else fn)
        leaves, treedef = _tree.tree_flatten((args, kwargs), is_leaf=_is_leaf)
        if _symbolic_cls is not None and any(
                isinstance(l, _symbolic_cls) for l in leaves):
            return _symbolic_handler(run, op_name, args, kwargs)
        t_pos = [i for i, l in enumerate(leaves) if isinstance(l, Tensor)]
        if not t_pos:
            # No Tensors. Raw arrays / tracers, or an ambient trace in
            # progress (creation/random ops under jit) => functional
            # passthrough so traced functions never return wrapped values.
            if any(isinstance(l, jax.Array) for l in leaves) or not _trace_clean():
                return run(*args, **kwargs)
            # Pure python inputs (creation/random ops): wrap for eager users.
            t0 = _maybe_t0()
            out_raw = run(*args, **kwargs)
            _post_op(out_raw, op_name, t0)
            return _wrap_outputs(out_raw)

        vals = list(leaves)
        for i in t_pos:
            vals[i] = leaves[i]._value

        record = (
            differentiable
            and engine.is_grad_enabled()
            and not any(_is_traced(vals[i]) for i in t_pos)
        )
        diff_pos = []
        if record:
            diff_pos = [
                i
                for i in t_pos
                if not leaves[i].stop_gradient
                and jnp.issubdtype(vals[i].dtype, jnp.inexact)
            ]
        if not diff_pos:
            a, k = _tree.tree_unflatten(treedef, vals)
            t0 = _maybe_t0()
            out_raw = run(*a, **k)
            if not any(_is_traced(v) for v in vals):
                _post_op(out_raw, op_name, t0)
            return _wrap_outputs(out_raw)

        diff_vals = [vals[i] for i in diff_pos]

        def pure(*dv):
            vv = list(vals)
            for i, v in zip(diff_pos, dv):
                vv[i] = v
            a, k = _tree.tree_unflatten(treedef, vv)
            return run(*a, **k)

        t0 = _maybe_t0()
        from ..core import random as _random

        rng_counter = _random.default_generator._counter
        out, vjp_fn = jax.vjp(pure, *diff_vals)
        # Same traced-input guard as the non-diff branch: non-Tensor leaves
        # can still be tracers (e.g. inside jax.checkpoint), and profiling
        # must not block_until_ready on a tracer.
        if not any(_is_traced(v) for v in vals):
            _post_op(out, op_name, t0)
        out_leaves, out_treedef = _tree.tree_flatten(out)
        out_avals = [
            _aval(l) if isinstance(l, jax.Array) else ((), jnp.float32)
            for l in out_leaves
        ]
        node = engine.GradNode(
            vjp_fn,
            [leaves[i] for i in diff_pos],
            out_treedef,
            out_avals,
            op_name=op_name,
            pure=pure,
            rng_counter=rng_counter,
        )
        return _wrap_outputs(out, node=node)

    op.__paddle_tpu_op__ = True
    return op


# Ops whose outputs are index/boolean-like or host objects: never taped.
NON_DIFFERENTIABLE: Set[str] = {
    "argmax", "argmin", "argsort", "searchsorted", "nonzero", "is_empty",
    "is_tensor", "is_complex", "is_floating_point", "is_integer", "shape",
    "rank", "numel", "equal", "equal_all", "not_equal", "greater_than",
    "greater_equal", "less_than", "less_equal", "logical_and", "logical_or",
    "logical_not", "logical_xor", "isfinite", "isinf", "isnan", "allclose",
    "isclose", "bernoulli", "multinomial", "poisson", "randint", "randperm",
    "unique", "sign", "floor_divide", "mod", "remainder",
    # host-boundary / integer-metadata ragged ops (tensor/segment.py)
    "sequence_pad", "sequence_unpad", "sequence_mask",
    "lengths_to_segment_ids",
}


def install_ops(namespace: dict) -> None:
    """Wrap every public callable in a namespace dict with make_op."""
    for key, val in list(namespace.items()):
        if key.startswith("_"):
            continue
        if isinstance(val, types.FunctionType) and not getattr(val, "__paddle_tpu_op__", False):
            namespace[key] = make_op(val, differentiable=key not in NON_DIFFERENTIABLE, op_name=key)


# ---------------------------------------------------------------------------
# Tensor indexing as a recorded op
# ---------------------------------------------------------------------------

def _getitem_raw(x, idx):
    return x[idx]


getitem = make_op(_getitem_raw, op_name="getitem")


# ---------------------------------------------------------------------------
# Method / operator surface installation
# ---------------------------------------------------------------------------

_METHOD_MODULES = (
    "math", "manipulation", "linalg", "logic", "search", "stat", "attribute", "creation",
)

# names that are properties or already defined on Tensor
_SKIP_METHODS = {
    "shape", "to_tensor", "numel", "clone", "T", "cast",
}

_BINOPS = {
    "__add__": "add", "__radd__": "add",
    "__sub__": "subtract", "__mul__": "multiply", "__rmul__": "multiply",
    "__truediv__": "divide", "__floordiv__": "floor_divide",
    "__mod__": "mod", "__pow__": "pow", "__matmul__": "matmul",
    "__eq__": "equal", "__ne__": "not_equal", "__lt__": "less_than",
    "__le__": "less_equal", "__gt__": "greater_than", "__ge__": "greater_equal",
    # bitwise dunders (math_op_patch.py parity): on bool tensors these are
    # the composable logical connectives (used by converted control flow)
    "__and__": "bitwise_and", "__rand__": "bitwise_and",
    "__or__": "bitwise_or", "__ror__": "bitwise_or",
    "__xor__": "bitwise_xor", "__rxor__": "bitwise_xor",
}


def install_methods(tensor_ns) -> None:
    """Attach the paddle.Tensor method surface, delegating to the wrapped ops.

    Mirrors varbase_patch_methods.py / math_op_patch.py: every tensor-namespace
    op whose first parameter is the tensor becomes ``x.op(...)``.
    """
    import inspect

    for name in dir(tensor_ns):
        if name.startswith("_") or name in _SKIP_METHODS:
            continue
        fn = getattr(tensor_ns, name)
        if not callable(fn) or not getattr(fn, "__paddle_tpu_op__", False):
            continue
        try:
            params = list(inspect.signature(fn).parameters)
        except (TypeError, ValueError):
            continue
        if not params or params[0] in ("data", "shape", "dtype", "equation", "start", "num_rows", "low"):
            continue  # creation-style ops: not methods
        if hasattr(Tensor, name):
            continue

        def make_method(f):
            def method(self, *args, **kwargs):
                return f(self, *args, **kwargs)

            method.__name__ = f.__name__
            method.__doc__ = f.__doc__
            return method

        setattr(Tensor, name, make_method(fn))

    # numel in paddle is a method returning a 0-d tensor
    def numel(self):
        out = tensor_ns.numel(self)
        return out if isinstance(out, Tensor) else tensor_ns.to_tensor(out, dtype="int64")

    Tensor.numel = numel

    def make_bin(f, reflected=False):
        def method(self, other):
            return f(other, self) if reflected else f(self, other)

        return method

    for dunder, opname in _BINOPS.items():
        fn = getattr(tensor_ns, opname)
        setattr(Tensor, dunder, make_bin(fn, reflected=dunder.startswith("__r")))

    # non-commutative reflected ops need explicit order swap
    def __rsub__(self, other):
        return tensor_ns.subtract(tensor_ns.to_tensor(other), self)

    def __rtruediv__(self, other):
        return tensor_ns.divide(tensor_ns.to_tensor(other), self)

    def __rpow__(self, other):
        return tensor_ns.pow(tensor_ns.to_tensor(other), self)

    def __rmatmul__(self, other):
        return tensor_ns.matmul(tensor_ns.to_tensor(other), self)

    def __neg__(self):
        return tensor_ns.scale(self, -1.0)

    def __abs__(self):
        return tensor_ns.abs(self)

    def __invert__(self):
        return tensor_ns.logical_not(self)

    Tensor.__rsub__ = __rsub__
    Tensor.__rtruediv__ = __rtruediv__
    Tensor.__rpow__ = __rpow__
    Tensor.__rmatmul__ = __rmatmul__
    Tensor.__neg__ = __neg__
    Tensor.__abs__ = __abs__
    Tensor.__invert__ = __invert__
    Tensor.__hash__ = object.__hash__


# -- static-graph bridge ----------------------------------------------------
# The paddle.static compat layer registers its Variable type + a handler;
# any op invoked with a symbolic Variable among its inputs is deferred into
# the graph instead of executed (framework.py Program-building parity).
_symbolic_cls = None
_symbolic_handler = None


def register_symbolic(cls, handler) -> None:
    global _symbolic_cls, _symbolic_handler
    _symbolic_cls = cls
    _symbolic_handler = handler
