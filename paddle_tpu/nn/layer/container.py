"""Container layers (reference: python/paddle/nn/layer/container.py +
fluid/dygraph/container.py: Sequential, LayerList, ParameterList, LayerDict).
"""
from __future__ import annotations

import collections
from typing import Iterable

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Parameter
from .layers import Layer


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, layer in layers[0].items():
                self.add_sublayer(name, layer)
        else:
            for i, item in enumerate(layers):
                if isinstance(item, tuple):
                    self.add_sublayer(item[0], item[1])
                else:
                    self.add_sublayer(str(i), item)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers.keys())
        return self._sub_layers[keys[idx]]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class _IteratedContainer(Layer):
    """A container whose owner iterates it and calls the members itself
    (``for layer in self.layers``): it never runs, so it cannot open its
    own ``jax.named_scope``.  Its members carry its scope in theirs
    (``layers/3``) instead, whichever of the two is registered first."""

    def _set_scope(self, scope: str) -> None:
        super()._set_scope(scope)
        for name, layer in self._sub_layers.items():
            if layer is not None:
                layer._set_scope(self._child_scope(name))

    def _child_scope(self, name: str) -> str:
        return name if self._scope is None else self._scope + "/" + name


class LayerList(_IteratedContainer):
    def __init__(self, sublayers: Iterable[Layer] = None):
        super().__init__()
        if sublayers is not None:
            for i, layer in enumerate(sublayers):
                self.add_sublayer(str(i), layer)

    def __len__(self):
        return len(self._sub_layers)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers.keys())
        return self._sub_layers[keys[idx]]

    def __setitem__(self, idx, layer):
        keys = list(self._sub_layers.keys())
        self.add_sublayer(keys[idx], layer)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer: Layer) -> "LayerList":
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index: int, layer: Layer) -> None:
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def extend(self, sublayers: Iterable[Layer]) -> "LayerList":
        for layer in sublayers:
            self.append(layer)
        return self


class ParameterList(Layer):
    def __init__(self, parameters: Iterable[Parameter] = None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __len__(self):
        return len(self._parameters)

    def __getitem__(self, idx):
        keys = list(self._parameters.keys())
        return self._parameters[keys[idx]]

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter: Parameter) -> "ParameterList":
        self.add_parameter(str(len(self._parameters)), parameter)
        return self


class LayerDict(_IteratedContainer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __len__(self):
        return len(self._sub_layers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __contains__(self, key):
        return key in self._sub_layers

    def __iter__(self):
        return iter(self._sub_layers)

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def clear(self):
        self._sub_layers.clear()

    def pop(self, key):
        layer = self._sub_layers[key]
        del self._sub_layers[key]
        return layer

    def update(self, sublayers) -> None:
        if isinstance(sublayers, dict):
            sublayers = sublayers.items()
        for item in sublayers:
            if not isinstance(item, (list, tuple)) or len(item) != 2:
                raise InvalidArgumentError("LayerDict.update expects (name, layer) pairs")
            self.add_sublayer(item[0], item[1])
