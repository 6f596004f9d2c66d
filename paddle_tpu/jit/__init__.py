"""``paddle_tpu.jit`` — trace-to-XLA: the static-graph replacement.

Reference parity: the whole dy2static + Executor vertical —
``fluid/dygraph/dygraph_to_static/program_translator.py:759`` (ProgramTranslator
+ ProgramCache), ``fluid/dygraph/jit.py:515,851`` (``paddle.jit.save/load`` →
TranslatedLayer), ``fluid/executor.py:916`` (Executor.run program cache) and
``fluid/compiler.py`` (CompiledProgram).

TPU-native design: there is no interpreted Program.  ``to_static`` wraps a
function/Layer so calls are traced once by ``jax.jit`` and compiled by XLA;
the jaxpr *is* the Program, the compiled executable *is* the CompiledProgram,
and XLA's cache (keyed on abstract input signature) replaces ProgramCache.
Layer parameters and buffers are threaded functionally through the traced
call (so optimizer updates between calls never retrace), a fresh PRNG key is
passed per call (so dropout/random ops advance — fixing the reference's
global-generator semantics the JAX way), and mutated buffers (BatchNorm
running stats) are returned as extra outputs and written back on the host.

``save``/``load`` serialize the traced computation as a StableHLO artifact
(``jax.export``) + a params file — the ProgramDesc+persistables analog that
the inference predictor consumes.
"""
from __future__ import annotations

import functools
import json
import os
import pickle
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags as _flags
from ..core.dtype import convert_dtype
from ..core.errors import InvalidArgumentError
from ..core.random import next_key, rng_guard
from ..framework import engine
from ..framework.dispatch import _wrap_outputs
from ..framework.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer

__all__ = [
    "to_static", "not_to_static", "StaticFunction", "InputSpec", "TrainStep",
    "MultiStepTrainStep", "DecodeSession", "DecodeMesh", "sample_logits",
    "FINISH_EOS", "FINISH_LENGTH", "classify_finish", "truncate_at_eos",
    "SpeculativeDecodeSession", "check_draft_compatible",
    "save", "load", "TranslatedLayer", "ProgramTranslator", "TracedLayer",
    "set_code_level", "set_verbosity", "enable_to_static",
]


class InputSpec:
    """paddle.static.InputSpec parity: symbolic input signature.

    ``None`` dims become export-time symbolic dimensions (dynamic batch).
    """

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = tuple(shape)
        self.dtype = convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tuple(tensor.shape), tensor.dtype, name=name)

    def __repr__(self):
        return "InputSpec(shape=%s, dtype=%s, name=%s)" % (
            self.shape, self.dtype, self.name)


def _unwrap(x):
    return x.value if isinstance(x, Tensor) else x


def _is_tensor(x) -> bool:
    return isinstance(x, Tensor)


class _StateBinding:
    """Collects (and later swaps) the Layers' parameters/buffers for a trace."""

    def __init__(self, layer: Optional[Layer]):
        self.layer = layer
        if layer is not None:
            self.param_items: List[Tuple[str, Parameter]] = list(layer.named_parameters())
            self.buffer_items: List[Tuple[str, Tensor]] = list(layer.named_buffers())
            self.sublayers = layer.sublayers(include_self=True)
        else:
            self.param_items, self.buffer_items, self.sublayers = [], [], []

    @property
    def params(self) -> List[Parameter]:
        return [p for _, p in self.param_items]

    @property
    def buffers(self) -> List[Tensor]:
        return [b for _, b in self.buffer_items]

    def mode_token(self) -> tuple:
        return tuple(l.training for l in self.sublayers)

    def swap_in(self, param_vals, buf_vals):
        saved = [t._value for t in self.params + self.buffers]
        for t, v in zip(self.params, param_vals):
            t._value = v
        for t, v in zip(self.buffers, buf_vals):
            t._value = v
        return saved

    def swap_out(self, saved):
        tensors = self.params + self.buffers
        new_buf_vals = [b._value for b in self.buffers]
        for t, v in zip(tensors, saved):
            t._value = v
        return new_buf_vals


def _find_layer(fn) -> Optional[Layer]:
    owner = getattr(fn, "__self__", None)
    return owner if isinstance(owner, Layer) else None


class StaticFunction:
    """The traced-callable handle (program_translator.py StaticFunction analog)."""

    def __init__(self, function: Callable, input_spec=None):
        if isinstance(function, Layer):
            self._layer = function
            self._function = function.forward
        else:
            self._layer = _find_layer(function)
            self._function = function
        self._input_spec = input_spec
        self._binding: Optional[_StateBinding] = None
        self._jitted = None
        functools.update_wrapper(self, self._function)

    def __get__(self, instance, owner=None):
        """Descriptor protocol so ``@to_static`` works on methods.

        ``class M(Layer): @to_static def forward(self, x)`` — attribute access
        binds the instance, and each instance gets its own traced cache.
        """
        if instance is None:
            return self
        cache = instance.__dict__.setdefault("_static_fn_cache", {})
        key = id(self)
        if key not in cache:
            bound = StaticFunction.__new__(StaticFunction)
            bound._layer = instance if isinstance(instance, Layer) else None
            bound._function = self._function.__get__(instance, owner)
            bound._input_spec = self._input_spec
            bound._binding = None
            bound._jitted = None
            functools.update_wrapper(bound, bound._function)
            cache[key] = bound
        return cache[key]

    # -- trace body -----------------------------------------------------
    def _ensure_binding(self):
        if self._binding is None:
            self._binding = _StateBinding(self._layer)
        return self._binding

    def _trace(self, param_vals, buf_vals, key, traced_leaves, static_leaves, mask, treedef, mode):
        binding = self._binding
        saved = binding.swap_in(param_vals, buf_vals)
        try:
            traced_it, static_it = iter(traced_leaves), iter(static_leaves)
            wrapped = [
                Tensor(next(traced_it), stop_gradient=True) if is_traced else next(static_it)
                for is_traced in mask
            ]
            args, kwargs = jax.tree_util.tree_unflatten(treedef, wrapped)
            with rng_guard(key):
                out = self._function(*args, **kwargs)
            out_raw = jax.tree_util.tree_map(_unwrap, out, is_leaf=_is_tensor)
        finally:
            new_buf_vals = binding.swap_out(saved)
        return out_raw, new_buf_vals

    def _get_jitted(self):
        if self._jitted is None or not _flags.get_flags(["FLAGS_jit_cache"])["FLAGS_jit_cache"]:
            self._jitted = jax.jit(self._trace, static_argnums=(4, 5, 6, 7))
        return self._jitted

    def __call__(self, *args, **kwargs):
        if not ProgramTranslator._enabled:
            # ProgramTranslator.enable(False): run the original function
            # eagerly (the reference's dygraph fallback)
            return self._function(*args, **kwargs)
        tracer_errors = (jax.errors.TracerBoolConversionError,
                         jax.errors.ConcretizationTypeError)
        try:
            return self._call_impl(*args, **kwargs)
        except tracer_errors as e:
            # data-dependent Python if/while hit at trace time: retry once
            # through the minimal AST conversion (the reference converts
            # up front via its ast_transformer stack; here conversion is
            # attempted on demand), else re-raise with the rewrite hint
            from . import dy2static

            if not getattr(self._function, "__dy2static_converted__",
                           False):
                try:
                    conv = dy2static.convert(self._function)
                except dy2static.ConversionError as ce:
                    raise RuntimeError(
                        dy2static.hint_for_tracer_error(e, self._function)
                        + " (auto-conversion: %s)" % ce) from e
                owner = getattr(self._function, "__self__", None)
                if owner is not None:
                    conv = conv.__get__(owner)
                # swap in the converted fn only for the retry; commit it
                # only on success so ProgramTranslator.enable(False)'s
                # eager fallback always runs the ORIGINAL function
                old_fn, old_jitted = self._function, self._jitted
                self._function, self._jitted = conv, None
                try:
                    return self._call_impl(*args, **kwargs)
                except tracer_errors as e2:
                    self._function, self._jitted = old_fn, old_jitted
                    raise RuntimeError(dy2static.hint_for_tracer_error(
                        e2, conv)) from e2
                except Exception:
                    self._function, self._jitted = old_fn, old_jitted
                    raise
            raise RuntimeError(dy2static.hint_for_tracer_error(
                e, self._function)) from e

    def _call_impl(self, *args, **kwargs):
        binding = self._ensure_binding()
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)
        # Partition: Tensors/arrays become traced inputs; python scalars and
        # other objects stay static (paddle dy2static treats non-tensor args
        # as Python values — shape/axis arguments must not become tracers).
        traced: List[Any] = []
        static: List[Any] = []
        mask: List[bool] = []
        arg_tensors: List[Tuple[int, Tensor]] = []
        for l in leaves:
            if isinstance(l, Tensor):
                arg_tensors.append((len(traced), l))
                traced.append(l._value)
                mask.append(True)
            elif isinstance(l, (jax.Array, np.ndarray)):
                traced.append(jnp.asarray(l))
                mask.append(True)
            else:
                static.append(l)
                mask.append(False)
        param_vals = [p._value for p in binding.params]
        buf_vals = [b._value for b in binding.buffers]
        key = next_key()
        mode = binding.mode_token()
        jitted = self._get_jitted()
        static_t, mask_t = tuple(static), tuple(mask)

        # Which inputs participate in eager autograd?
        record = engine.is_grad_enabled() and not any(
            isinstance(v, jax.core.Tracer) for v in param_vals + traced
        )
        diff_params = [
            (i, p) for i, p in enumerate(binding.params)
            if record and not p.stop_gradient and jnp.issubdtype(p._value.dtype, jnp.inexact)
        ]
        diff_args = [
            (i, t) for i, t in arg_tensors
            if record and not t.stop_gradient and jnp.issubdtype(t._value.dtype, jnp.inexact)
        ]

        if not diff_params and not diff_args:
            out_raw, new_bufs = jitted(
                param_vals, buf_vals, key, tuple(traced), static_t, mask_t, treedef, mode
            )
            self._writeback_buffers(new_bufs)
            return _wrap_outputs(out_raw)

        np_ = len(diff_params)

        def pure(*dv):
            pv = list(param_vals)
            for (i, _), v in zip(diff_params, dv[:np_]):
                pv[i] = v
            al = list(traced)
            for (i, _), v in zip(diff_args, dv[np_:]):
                al[i] = v
            out_raw, new_bufs = jitted(
                pv, buf_vals, key, tuple(al), static_t, mask_t, treedef, mode
            )
            return out_raw, new_bufs

        diff_vals = [p._value for _, p in diff_params] + [t._value for _, t in diff_args]
        (out_raw, new_bufs), vjp_fn = jax.vjp(pure, *diff_vals, has_aux=False)
        self._writeback_buffers(new_bufs)

        # Tape node: cotangents for new_bufs are zeros (stop-gradient state).
        out_leaves, out_treedef = jax.tree_util.tree_flatten((out_raw, new_bufs))
        out_avals = [
            ((tuple(l.shape), l.dtype) if isinstance(l, jax.Array) else ((), jnp.float32))
            for l in out_leaves
        ]
        node = engine.GradNode(
            vjp_fn,
            [p for _, p in diff_params] + [t for _, t in diff_args],
            out_treedef,
            out_avals,
            op_name="to_static(%s)" % getattr(self._function, "__name__", "fn"),
        )
        wrapped_out, _ = _wrap_outputs((out_raw, new_bufs), node=node)
        return wrapped_out

    def _writeback_buffers(self, new_bufs) -> None:
        for b, v in zip(self._binding.buffers, new_bufs):
            if isinstance(v, jax.Array) and not isinstance(v, jax.core.Tracer):
                b._replace_value(v)

    # -- introspection / parity -----------------------------------------
    @property
    def concrete_program(self):
        raise NotImplementedError(
            "there is no interpreted Program; inspect the jaxpr via "
            "jax.make_jaxpr on the wrapped function instead"
        )

    def rollback(self):
        return self._function


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """``@paddle.jit.to_static`` parity decorator (trace-to-XLA)."""

    def decorate(fn):
        return StaticFunction(fn, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn=None):
    """Parity no-op: everything traces; nothing needs exclusion."""
    return fn


# ---------------------------------------------------------------------------
# TrainStep — the fused, donated, jitted training step
# ---------------------------------------------------------------------------

class TrainStep:
    """One-compile training step: forward + backward + optimizer update.

    The TPU-native analog of the reference's CompiledProgram training path
    (``fluid/compiler.py`` + ParallelExecutor): parameters, optimizer state
    and mutable buffers are threaded functionally, donated to XLA so updates
    are in-place in HBM, and the loss is the only host-visible output.

    ``loss_fn(model, *batch) -> scalar Tensor``.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, donate: Optional[bool] = None):
        self._model = model
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._binding = _StateBinding(model)
        params = self._binding.params
        if optimizer._parameter_list is None:
            optimizer._parameter_list = params
        # materialize optimizer state eagerly so the jitted step sees a
        # concrete pytree structure; order by the model's parameter walk so
        # states/grads/params stay aligned regardless of the order the user
        # passed parameters to the optimizer
        opt_ids = {id(p) for p in optimizer._parameter_list if not p.stop_gradient}
        self._opt_params = [p for p in params if id(p) in opt_ids]
        if len(self._opt_params) != len(opt_ids):
            raise InvalidArgumentError(
                "TrainStep: optimizer tracks %d trainable parameters that are "
                "not parameters of the model" % (len(opt_ids) - len(self._opt_params))
            )
        for p in self._opt_params:
            optimizer._state_for(p)
        # ZeRO-offload support: states that live in host memory (sharding
        # memory_kind='pinned_host') are streamed to device for the update
        # inside the trace and streamed back after — XLA turns these
        # device_puts into async PCIe copies overlapping the step
        self._state_host_shardings = None
        if donate is None:
            donate = _flags.get_flags(["FLAGS_use_donated_buffers"])["FLAGS_use_donated_buffers"]
        # offloaded (host-resident) states are excluded from donation: they
        # hold no HBM, and PjRt aborts on aliasing a pinned_host input buffer
        # into the device-space update dataflow
        states_offloaded = any(
            getattr(getattr(v, "sharding", None), "memory_kind", None)
            == "pinned_host"
            for p in self._opt_params
            for v in jax.tree.leaves(optimizer._states[p.name]))
        donate_argnums = ((0, 2) if states_offloaded else (0, 1, 2)) \
            if donate else ()
        self._donate_argnums = donate_argnums
        self._jitted = jax.jit(self._step, static_argnums=(5,), donate_argnums=donate_argnums)

    def _step(self, param_vals, opt_states, buf_vals, key, lr, mode, batch_leaves):
        binding = self._binding
        opt = self._optimizer
        params = binding.params
        opt_ids = {id(p) for p in self._opt_params}
        diff_idx = [i for i, p in enumerate(params) if id(p) in opt_ids]

        def forward(dv):
            pv = list(param_vals)
            for i, v in zip(diff_idx, dv):
                pv[i] = v
            saved = binding.swap_in(pv, buf_vals)
            try:
                batch = [
                    Tensor(l, stop_gradient=True) if isinstance(l, jax.Array) else l
                    for l in batch_leaves
                ]
                # ``loss`` and ``optimizer`` name the step's two
                # halves in a device profile; inside ``loss`` the module
                # tree names the layers (nn.Layer.__call__), and backward
                # operations keep their forward scope under
                # ``transpose(jvp(...))``
                with rng_guard(key), jax.named_scope("loss"):
                    loss = self._loss_fn(self._model, *batch)
                loss_raw = _unwrap(loss)
            finally:
                new_bufs = binding.swap_out(saved)
            return loss_raw, new_bufs

        diff_vals = [param_vals[i] for i in diff_idx]
        (loss, new_bufs), grads = jax.value_and_grad(forward, has_aux=True)(diff_vals)

        diff_params = [params[i] for i in diff_idx]
        host_sh = self._state_host_shardings
        if host_sh is not None:
            opt_states = jax.tree.map(
                lambda x, s: x if s is False else jax.device_put(
                    x, s.with_memory_kind("device")),
                opt_states, host_sh)
        with jax.named_scope("optimizer"):
            new_diff_vals, new_states = opt._functional_step(
                diff_params, diff_vals, grads, opt_states, lr
            )
        # (transfer back to host happens outside the jit boundary in
        # __call__ — in-trace device_put-to-host is not reliably reflected
        # in the executable's output memory space)
        new_param_vals = list(param_vals)
        for i, v in zip(diff_idx, new_diff_vals):
            new_param_vals[i] = v
        return loss, new_param_vals, new_states, new_bufs

    def __call__(self, *batch):
        binding = self._binding
        opt = self._optimizer
        param_vals = [p._value for p in binding.params]
        buf_vals = [b._value for b in binding.buffers]
        opt_states = [opt._states[p.name] for p in self._opt_params]

        def _host_sharding(x):
            # False (a pytree leaf, unlike None) marks device-resident states
            sh = getattr(x, "sharding", None)
            return sh if getattr(sh, "memory_kind", None) == "pinned_host" \
                else False

        host_sh = jax.tree.map(_host_sharding, opt_states)
        self._state_host_shardings = (
            host_sh if any(s is not False for s in jax.tree.leaves(host_sh))
            else None)
        key = next_key()
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        mode = binding.mode_token()
        batch_leaves = [_unwrap(b) for b in batch]
        loss, new_param_vals, new_states, new_bufs = self._jitted(
            param_vals, opt_states, buf_vals, key, lr, mode, batch_leaves
        )
        for p, v in zip(binding.params, new_param_vals):
            p._replace_value(v)
        host_flags = self._state_host_shardings
        for i, (p, s) in enumerate(zip(self._opt_params, new_states)):
            if host_flags is not None:
                s = jax.tree.map(
                    lambda x, hs: x if hs is False else jax.device_put(x, hs),
                    s, host_flags[i])
            opt._states[p.name] = s
        for b, v in zip(binding.buffers, new_bufs):
            b._replace_value(v)
        return Tensor(loss, stop_gradient=True)


class MultiStepTrainStep(TrainStep):
    """K optimizer steps per dispatch, inside ONE jitted call.

    ``lax.scan`` over the leading axis of every batch leaf: each batch
    input is stacked ``[K, ...]`` and the parameters/optimizer
    states/buffers thread through the scan carry, fully donated, with the
    per-step RNG keys split from one dispatch key.  Returns the ``[K]``
    per-step losses.

    TPU-native rationale: a single-step dispatch pays host→device launch
    latency per optimizer step, which can dominate a short step.
    Batching K steps amortizes it to 1/K without changing
    the math — the same trick the reference's Executor achieves by
    running a multi-iteration Program per ``run()``
    (``fluid/executor.py:1`` run-loop semantics).

    Caveats: the learning rate is read once per DISPATCH, so an
    LRScheduler advances per K steps (call ``scheduler.step(K)`` or keep
    K small relative to the schedule's granularity); per-step host-side
    callbacks cannot observe intermediate states.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 steps_per_call: int, donate: Optional[bool] = None):
        if steps_per_call < 1:
            raise InvalidArgumentError(
                "MultiStepTrainStep: steps_per_call must be >= 1, got %r"
                % (steps_per_call,))
        super().__init__(model, loss_fn, optimizer, donate=donate)
        if any(getattr(getattr(v, "sharding", None), "memory_kind", None)
               == "pinned_host"
               for p in self._opt_params
               for v in jax.tree.leaves(optimizer._states[p.name])):
            # _step's in-trace device_put of offloaded states would make
            # the scan carry's input and output memory kinds disagree
            raise InvalidArgumentError(
                "MultiStepTrainStep does not support pinned_host "
                "(ZeRO-offload) optimizer states; use TrainStep for the "
                "offloaded path")
        self.steps_per_call = steps_per_call
        self._jitted = jax.jit(self._multi, static_argnums=(5,),
                               donate_argnums=self._donate_argnums)

    def _multi(self, param_vals, opt_states, buf_vals, key, lr, mode,
               batch_leaves):
        def body(carry, leaves):
            pv, st, bv, key = carry
            key, sub = jax.random.split(key)
            loss, pv, st, bv = self._step(pv, st, bv, sub, lr, mode,
                                          list(leaves))
            return (pv, st, bv, key), loss

        (pv, st, bv, _), losses = jax.lax.scan(
            body, (param_vals, opt_states, buf_vals, key), batch_leaves)
        return losses, pv, st, bv

    # the K-stacking contract, spelled out in every shape error so the
    # batch==K aliasing case is diagnosable from the message alone
    # (ADVICE r5 low: an unstacked [batch, ...] input whose batch
    # happens to equal K passes the leading-dim check and silently
    # scans over the BATCH axis, training on single examples)
    _STACK_CONTRACT = (
        "each batch input must be K per-STEP batches stacked along a NEW "
        "leading axis (np.stack -> [K, batch, ...]); a plain [batch, ...] "
        "input is never valid here — if your per-step batch size equals "
        "K, the leading dim would alias the batch axis and the scan "
        "would train on single examples")

    def __call__(self, *batch):
        k = self.steps_per_call
        for i, b in enumerate(batch):
            shape = getattr(_unwrap(b), "shape", None)
            if shape is None or len(shape) == 0:
                raise InvalidArgumentError(
                    "MultiStepTrainStep: batch input %d is a scalar; "
                    "scan needs a [%d, ...] leading step axis — %s "
                    "(or close over constants in loss_fn)"
                    % (i, k, self._STACK_CONTRACT))
            if shape[0] != k:
                raise InvalidArgumentError(
                    "MultiStepTrainStep(steps_per_call=%d): batch input "
                    "%d has shape %s, leading dim %s != K=%d; %s"
                    % (k, i, shape, shape[0], k, self._STACK_CONTRACT))
        return super().__call__(*batch)


# ---------------------------------------------------------------------------
# save / load — StableHLO artifact (ProgramDesc + persistables analog)
# ---------------------------------------------------------------------------

_ARTIFACT_SUFFIX = ".pdmodel.stablehlo"
_PARAMS_SUFFIX = ".pdiparams.npz"
_META_SUFFIX = ".pdmodel.json"


def _specs_from_input_spec(input_spec) -> List[jax.ShapeDtypeStruct]:
    from jax import export as jax_export

    # Name resolution: a ``None``/-1 at axis 0 is the shared batch symbol "b"
    # (paddle convention: multiple inputs share the batch dim); elsewhere each
    # gets a unique symbol.  A *string* dim is an explicit symbol name —
    # equal names are constrained equal across inputs.
    shapes_dtypes = []
    dim_names = []  # per (input, axis): None for static, else symbol name
    ordered_names: List[str] = []
    for j, spec in enumerate(input_spec):
        if isinstance(spec, InputSpec):
            shape, dtype = spec.shape, spec.dtype
        else:
            shape, dtype = tuple(spec.shape), spec.dtype
        names = []
        for i, d in enumerate(shape):
            if isinstance(d, str):
                name = d
            elif d is None or (isinstance(d, int) and d < 0):
                name = "b" if i == 0 else "d%d_%d" % (j, i)
            else:
                name = None
            names.append(name)
            if name is not None and name not in ordered_names:
                ordered_names.append(name)
        shapes_dtypes.append((shape, dtype))
        dim_names.append(names)

    # all symbolic dims must share ONE export scope
    sym_by_name = {}
    if ordered_names:
        dims = jax_export.symbolic_shape(",".join(ordered_names))
        sym_by_name = dict(zip(ordered_names, dims))

    specs = []
    for (shape, dtype), names in zip(shapes_dtypes, dim_names):
        dims = [
            sym_by_name[n] if n is not None else int(d)
            for d, n in zip(shape, names)
        ]
        specs.append(jax.ShapeDtypeStruct(tuple(dims), dtype))
    return specs


def save(layer, path: str, input_spec=None, **config) -> None:
    """``paddle.jit.save`` parity (fluid/dygraph/jit.py:515).

    Writes three files: ``<path>.pdmodel.stablehlo`` (serialized StableHLO
    program via jax.export — the ProgramDesc analog), ``<path>.pdiparams.npz``
    (parameters + persistable buffers), ``<path>.pdmodel.json`` (metadata).

    ``params_const=True`` bakes parameters/buffers into the program as
    constants instead of runtime arguments. This is the TPU-native
    analog of the reference's inference fusion/const-fold pass family
    (``framework/ir/conv_bn_fuse_pass.cc:1`` and friends): with weights
    constant, XLA's simplifier can fold eval-mode BatchNorm scales into
    the preceding conv/matmul weights and pre-evaluate every
    param-only subexpression at compile time — none of which is legal
    when params arrive as arguments. The artifact is self-contained;
    ``set_state_dict`` on the loaded layer cannot retarget it (weights
    live in the program), which ``jit.load`` enforces.
    """
    from jax import export as jax_export

    if isinstance(layer, StaticFunction):
        fn = layer._function
        owner = layer._layer
        if input_spec is None:
            input_spec = layer._input_spec
    elif isinstance(layer, Layer):
        fn = layer.forward
        owner = layer
    elif callable(layer):
        fn = layer
        owner = _find_layer(layer)
    else:
        raise InvalidArgumentError("jit.save expects a Layer or function, got %r" % type(layer))

    binding = _StateBinding(owner)
    if input_spec is None:
        raise InvalidArgumentError(
            "jit.save requires input_spec=[InputSpec(shape, dtype), ...] "
            "(or example Tensors) to fix the traced signature"
        )
    arg_specs = _specs_from_input_spec(input_spec)
    param_names = [n for n, _ in binding.param_items]
    buffer_names = [n for n, _ in binding.buffer_items]
    param_vals = [p._value for p in binding.params]
    buf_vals = [b._value for b in binding.buffers]

    def infer(param_vals, buf_vals, *args):
        saved = binding.swap_in(param_vals, buf_vals)
        try:
            wrapped = [Tensor(a, stop_gradient=True) for a in args]
            with rng_guard(jax.random.key(0)):
                out = fn(*wrapped)
            out_raw = jax.tree_util.tree_map(_unwrap, out, is_leaf=_is_tensor)
        finally:
            binding.swap_out(saved)
        return out_raw

    params_const = bool(config.pop("params_const", False))

    was_training = [l.training for l in binding.sublayers]
    if owner is not None:
        owner.eval()
    try:
        # Multi-platform lowering: the artifact must load on any backend
        # (train on TPU, serve on CPU — AnalysisPredictor portability parity).
        if params_const:
            # closing over the concrete arrays embeds them as program
            # constants — the whole point (see docstring)
            fn_to_export = jax.jit(
                lambda *args: infer(param_vals, buf_vals, *args))
            export_specs = tuple(arg_specs)
        else:
            fn_to_export = jax.jit(infer)
            export_specs = (
                [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in param_vals],
                [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in buf_vals],
            ) + tuple(arg_specs)
        exporter = jax_export.export(fn_to_export, platforms=("cpu", "tpu", "cuda"))
        exported = exporter(*export_specs)
    finally:
        for l, t in zip(binding.sublayers, was_training):
            l.training = t

    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    with open(path + _ARTIFACT_SUFFIX, "wb") as f:
        f.write(exported.serialize())
    if params_const:
        # weights already live inside the program; an .npz copy would
        # double the artifact on disk and, at load, in device memory
        arrays = {}
    else:
        arrays = {"param:" + n: np.asarray(v)
                  for n, v in zip(param_names, param_vals)}
        arrays.update({"buffer:" + n: np.asarray(v)
                       for n, v in zip(buffer_names, buf_vals)})
    np.savez(path + _PARAMS_SUFFIX, **arrays)
    meta = {
        "format": "paddle_tpu.jit/1",
        "platforms": list(exported.platforms),
        "param_names": param_names,
        "buffer_names": buffer_names,
        "n_inputs": len(arg_specs),
        "params_const": params_const,
    }
    with open(path + _META_SUFFIX, "w") as f:
        json.dump(meta, f)


class TranslatedLayer(Layer):
    """A loaded artifact, callable like a Layer (fluid/dygraph/io.py parity).

    Inference-only: outputs are stop_gradient (use the original Layer class +
    ``set_state_dict`` for fine-tuning; artifact fine-tune parity is a
    documented delta — XLA artifacts carry no grad program).
    """

    def __init__(self, exported, param_arrays, buffer_arrays, meta):
        super().__init__()
        self._exported = exported
        self._meta = meta
        if meta.get("params_const"):
            # weights are program constants: registering the (absent) .npz
            # copies would only duplicate them in device memory
            self._param_keys = []
            self._buffer_keys = []
            return
        self._param_keys = [n.replace(".", "__") for n in meta["param_names"]]
        self._buffer_keys = [n.replace(".", "__") for n in meta["buffer_names"]]
        for key, v in zip(self._param_keys, param_arrays):
            self._parameters[key] = Parameter(jnp.asarray(v), trainable=False)
        for key, v in zip(self._buffer_keys, buffer_arrays):
            self.register_buffer(key, Tensor(jnp.asarray(v), stop_gradient=True))

    def forward(self, *args):
        raw = [_unwrap(a) for a in args]
        if self._meta.get("params_const"):
            # weights live INSIDE the program (jit.save(params_const=True))
            out = self._exported.call(*raw)
            return _wrap_outputs(out)
        # read live state so set_state_dict takes effect
        param_vals = [self._parameters[k]._value for k in self._param_keys]
        buf_vals = [self._buffers[k]._value for k in self._buffer_keys]
        out = self._exported.call(param_vals, buf_vals, *raw)
        return _wrap_outputs(out)

    def set_state_dict(self, state_dict, *args, **kwargs):
        if self._meta.get("params_const"):
            raise InvalidArgumentError(
                "this artifact was saved with params_const=True: its "
                "weights are program constants and cannot be retargeted; "
                "re-export with params_const=False for a swappable-weights "
                "artifact")
        return super().set_state_dict(state_dict, *args, **kwargs)

    # rebind the paddle-parity aliases: the base class binds them to ITS
    # set_state_dict, which would silently bypass the const-artifact guard
    set_dict = set_state_dict
    load_dict = set_state_dict


def load(path: str, **config) -> TranslatedLayer:
    """``paddle.jit.load`` parity (fluid/dygraph/jit.py:851)."""
    from jax import export as jax_export

    with open(path + _META_SUFFIX) as f:
        meta = json.load(f)
    with open(path + _ARTIFACT_SUFFIX, "rb") as f:
        exported = jax_export.deserialize(f.read())
    if meta.get("params_const"):
        params, buffers = [], []  # weights live inside the program
    else:
        data = np.load(path + _PARAMS_SUFFIX)
        params = [data["param:" + n] for n in meta["param_names"]]
        buffers = [data["buffer:" + n] for n in meta["buffer_names"]]
    return TranslatedLayer(exported, params, buffers, meta)


def set_code_level(level: int = 100, also_to_stdout: bool = False) -> None:
    """dy2static debugging-API parity: the trace-based pipeline has no
    transformed source code to print; retained as an accepted no-op."""


def set_verbosity(level: int = 0, also_to_stdout: bool = False) -> None:
    """dy2static debugging-API parity (see set_code_level)."""


class ProgramTranslator:
    """program_translator.py:759 parity: global dygraph→static switch.

    ``enable(False)`` makes ``to_static``-decorated functions run eagerly
    (the reference's fallback interpreter path == our eager tape).
    """

    _instance = None
    _enabled = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static: bool) -> None:
        type(self)._enabled = bool(enable_to_static)

    @property
    def enable_to_static(self) -> bool:
        return type(self)._enabled


def enable_to_static(enable: bool = True) -> None:
    """paddle.jit.enable_to_static parity."""
    ProgramTranslator.get_instance().enable(enable)


class TracedLayer:
    """fluid/dygraph/jit.py TracedLayer parity over to_static machinery:
    trace once with example inputs, then run/save the traced program."""

    def __init__(self, static_fn, examples):
        self._fn = static_fn
        self._examples = examples

    @classmethod
    def trace(cls, layer, inputs):
        inputs = list(inputs)
        fn = to_static(lambda *a: layer(*a))
        out = fn(*inputs)
        return out, cls(fn, inputs)

    def __call__(self, *args):
        return self._fn(*args)

    def save_inference_model(self, path, feed=None, fetch=None):
        specs = [InputSpec.from_tensor(t) if hasattr(t, "value") else t
                 for t in self._examples]
        save(self._fn, path, input_spec=specs)


# the decode engine imports _StateBinding back from this module, so it
# loads after everything above is defined
from .mesh import DecodeMesh  # noqa: E402,F401
from .decode import (  # noqa: E402,F401
    FINISH_EOS, FINISH_LENGTH, DecodeSession, classify_finish,
    sample_logits, truncate_at_eos)
from .speculative import (  # noqa: E402,F401
    SpeculativeDecodeSession, check_draft_compatible)
