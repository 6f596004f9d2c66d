"""``paddle_tpu.nn.Layer`` — the module/layer base class.

Reference parity: ``python/paddle/fluid/dygraph/layers.py:81`` (Layer:
parameters/sublayers/buffers/hooks/state_dict/train-eval/apply/to) and
ParamAttr (``fluid/param_attr.py``).

TPU-native notes: parameters are :class:`framework.Parameter` (immutable
jax.Array values, functionally swappable), so the same Layer object serves
both eager taped execution and jit-functionalized execution (paddle_tpu.jit
binds tracer values into the parameters for the duration of a trace).
"""
from __future__ import annotations

import collections
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dtype import convert_dtype, get_default_dtype
from ...core.errors import InvalidArgumentError
from ...framework.tensor import Parameter, Tensor
from .. import initializer as I

# ``Layer.__call__`` names operations by ``jax.named_scope``, which is
# metadata, and JAX's persistent compile cache leaves metadata out of its
# key unless told otherwise: an executable cached before a scope existed
# (or under another name) would be served again without it, and a device
# profile would read stale names.  The price is a recompile when a traced
# line moves.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


class ParamAttr:
    """paddle.ParamAttr parity (fluid/param_attr.py)."""

    def __init__(
        self,
        name: Optional[str] = None,
        initializer=None,
        learning_rate: float = 1.0,
        regularizer=None,
        trainable: bool = True,
        do_model_average: bool = True,
        need_clip: bool = True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.do_model_average = do_model_average
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr) -> Optional["ParamAttr"]:
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        if attr is False:
            return None
        raise InvalidArgumentError("unsupported param_attr: %r" % (attr,))


class HookRemoveHelper:
    def __init__(self, hooks: dict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self) -> None:
        self._hooks.pop(self._hook_id, None)


class Layer:
    """Base class for all network layers (fluid/dygraph/layers.py:81 analog)."""

    # the name this layer's parent registered it under (None for a root):
    # ``__call__`` runs ``forward`` under ``jax.named_scope`` of it, so
    # every jitted step carries the module tree in its operations'
    # ``op_name`` (``encoder/layers/3/self_attn/q_proj/dot_general``) and
    # a device profile can be read by layer.  Metadata only: it changes
    # no executable's name and no compile count.
    _scope: Optional[str] = None

    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        self.training = True
        # canonical string always (paddle's Layer._dtype is a string;
        # ported code compares it to 'float32'-style literals)
        self._dtype = np.dtype(convert_dtype(dtype)).name if dtype \
            is not None else get_default_dtype()
        self._parameters: Dict[str, Optional[Parameter]] = collections.OrderedDict()
        self._sub_layers: Dict[str, Optional["Layer"]] = collections.OrderedDict()
        self._buffers: Dict[str, Optional[Tensor]] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or type(self).__name__.lower()

    # -- construction helpers -------------------------------------------
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias: bool = False,
        default_initializer=None,
    ) -> Optional[Parameter]:
        attr = ParamAttr._to_attr(attr)
        if attr is None:  # attr=False disables (e.g. bias_attr=False)
            return None
        dtype = convert_dtype(dtype) or self._dtype
        init = attr.initializer or default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        value = init(tuple(int(s) for s in shape), dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        p.regularizer = attr.regularizer
        p.do_model_average = attr.do_model_average
        p.need_clip = attr.need_clip
        return p

    def create_tensor(self, name=None, dtype=None, fill_value=0.0) -> Tensor:
        dtype = convert_dtype(dtype) or self._dtype
        return Tensor(jnp.full((), fill_value, dtype), stop_gradient=True, name=name)

    def add_parameter(self, name: str, parameter: Optional[Parameter]) -> Optional[Parameter]:
        if parameter is not None and not isinstance(parameter, Parameter):
            raise InvalidArgumentError("add_parameter expects a Parameter, got %r" % type(parameter))
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer") -> "Layer":
        if not isinstance(sublayer, Layer):
            raise InvalidArgumentError("add_sublayer expects a Layer, got %r" % type(sublayer))
        self._sub_layers[str(name)] = sublayer
        sublayer._set_scope(self._child_scope(str(name)))
        return sublayer

    def _set_scope(self, scope: str) -> None:
        object.__setattr__(self, "_scope", scope)

    def _child_scope(self, name: str) -> str:
        """The scope of a sublayer registered as ``name``.  A layer that
        is called contributes its own scope when it runs, so its
        children carry their bare names (containers that are only
        iterated differ: see ``LayerList``)."""
        return name

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True) -> None:
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor, stop_gradient=True, name=name)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            self._non_persistable_buffer_names.discard(name)

    # -- attribute magic -------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise InvalidArgumentError("call Layer.__init__ before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise InvalidArgumentError("call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            value._set_scope(self._child_scope(name))
        elif buffers is not None and name in buffers:
            if value is not None and not isinstance(value, Tensor):
                value = Tensor(value, stop_gradient=True, name=name)
            buffers[name] = value
        else:
            if params is not None and name in params:
                if value is None:
                    params[name] = None
                    return
                raise InvalidArgumentError(
                    "cannot overwrite parameter %r with a non-Parameter; use "
                    "param.set_value(...) or assign a Parameter" % name
                )
            if layers is not None and name in layers and not isinstance(value, Layer) and value is not None:
                raise InvalidArgumentError("cannot overwrite sublayer %r with %r" % (name, type(value)))
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        # only called when normal lookup fails
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError("'%s' object has no attribute '%s'" % (type(self).__name__, name))

    def __delattr__(self, name: str) -> None:
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = []
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d:
                extra.extend(d.keys())
        return list(super().__dir__()) + extra

    # -- iteration -------------------------------------------------------
    def named_parameters(
        self, prefix: str = "", include_sublayers: bool = True
    ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for layer_name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (layer_name + "." + pname if layer_name else pname), p

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_sublayers(
        self, prefix: str = "", include_self: bool = False
    ) -> Iterator[Tuple[str, "Layer"]]:
        seen = set()

        def walk(layer, name):
            if id(layer) in seen:
                return
            seen.add(id(layer))
            yield name, layer
            for sub_name, sub in layer._sub_layers.items():
                if sub is None:
                    continue
                yield from walk(sub, name + "." + sub_name if name else sub_name)

        gen = walk(self, prefix)
        if not include_self:
            first = next(gen, None)
            if first is None:
                return
        yield from gen

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def children(self) -> Iterator["Layer"]:
        for _, sub in self.named_children():
            yield sub

    def named_buffers(
        self, prefix: str = "", include_sublayers: bool = True
    ) -> Iterator[Tuple[str, Tensor]]:
        for layer_name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for bname, b in layer._buffers.items():
                if b is None:
                    continue
                yield (layer_name + "." + bname if layer_name else bname), b

    def buffers(self, include_sublayers: bool = True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    # -- state dict ------------------------------------------------------
    def state_dict(
        self,
        destination=None,
        include_sublayers: bool = True,
        structured_name_prefix: str = "",
        use_hook: bool = True,
    ):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            short = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = owner._sub_layers[part]
            if short in owner._non_persistable_buffer_names:
                continue
            dest[structured_name_prefix + name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        missing, unexpected = [], []
        # base-class walk on purpose: instance-level state_dict shadows
        # (amp.decorate save_dtype) must not redirect load targets to copies
        own = Layer.state_dict(self)
        matched = set()
        for key, value in state_dict.items():
            if key not in own:
                unexpected.append(key)
                continue
            target = own[key]
            v = value.value if isinstance(value, Tensor) else jnp.asarray(np.asarray(value))
            if tuple(v.shape) != tuple(target.value.shape):
                raise InvalidArgumentError(
                    "state_dict shape mismatch for %s: %s vs %s"
                    % (key, tuple(v.shape), tuple(target.value.shape))
                )
            target._replace_value(v.astype(target.value.dtype))
            matched.add(key)
        missing = [k for k in own if k not in matched]
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- mode / traversal ------------------------------------------------
    def train(self) -> "Layer":
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self) -> "Layer":
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    def to(self, device=None, dtype=None, blocking=None) -> "Layer":
        dtype = convert_dtype(dtype)
        if dtype is not None:
            for p in self.parameters():
                p._replace_value(p.value.astype(dtype))
            for b in self.buffers():
                if jnp.issubdtype(b.value.dtype, jnp.floating):
                    b._replace_value(b.value.astype(dtype))
            for layer in self.sublayers(include_self=True):
                layer._dtype = np.dtype(dtype).name
        return self

    def astype(self, dtype) -> "Layer":
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks -----------------------------------------------------------
    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            "%s must implement forward()" % type(self).__name__
        )

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        if self._scope is None:
            outputs = self.forward(*inputs, **kwargs)
        else:
            with jax.named_scope(self._scope):
                outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # -- misc ------------------------------------------------------------
    def full_name(self) -> str:
        return self._name_scope

    def extra_repr(self) -> str:
        return ""

    def __repr__(self) -> str:
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_repr = repr(sub).split("\n")
            lines.append("(%s): %s" % (name, sub_repr[0]))
            lines.extend("  " + l for l in sub_repr[1:])
        main = type(self).__name__ + "(" + extra
        if lines:
            return main + "\n  " + "\n  ".join(lines) + "\n)"
        return main + ")"

    def clear_gradients(self) -> None:
        for p in self.parameters():
            p.clear_grad()
