"""Normalization layers (reference: python/paddle/nn/layer/norm.py; BatchNorm
kernel batch_norm_op.cc, SyncBatchNorm sync_batch_norm_op.cu).

BatchNorm running stats live in buffers; the update is functional (the pure
triple-return ``functional.norm.batch_norm``) and written back with
``set_value`` — eager mode updates eagerly, and under a ``paddle_tpu.jit``
trace the bound buffer tracers are captured as extra outputs (mutable-state
threading), so the same layer works in both worlds.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Tensor
from .. import functional as F
from ..functional import norm as _norm_impl
from .. import initializer as I
from .layers import Layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon: float = 1e-5, weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        self.weight = (
            None if weight_attr is False
            else self.create_parameter(self._normalized_shape, attr=weight_attr, default_initializer=I.Constant(1.0))
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter(self._normalized_shape, attr=bias_attr, is_bias=True)
        )

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias, self._epsilon)

    def extra_repr(self):
        return "normalized_shape=%s, epsilon=%s" % (self._normalized_shape, self._epsilon)


class _BatchNormBase(Layer):
    def __init__(
        self,
        num_features: int,
        momentum: float = 0.9,
        epsilon: float = 1e-5,
        weight_attr=None,
        bias_attr=None,
        data_format: str = "NCHW",
        use_global_stats=None,
        name=None,
    ):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = (
            None if weight_attr is False
            else self.create_parameter([num_features], attr=weight_attr, default_initializer=I.Constant(1.0))
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter([num_features], attr=bias_attr, is_bias=True)
        )
        self.register_buffer("_mean", Tensor(jnp.zeros([num_features]), name="mean"))
        self.register_buffer("_variance", Tensor(jnp.ones([num_features]), name="variance"))

    def _check_input_dim(self, x):
        pass

    def forward(self, x):
        self._check_input_dim(x)
        out, _, _ = self._bn(x)
        return out

    def _bn(self, x):
        # dispatch-wrapped pure triple-return impl
        from ..functional import _bn_triple

        out, new_mean, new_var = _bn_triple(
            x, self._mean, self._variance, self.weight, self.bias,
            self.training, self._momentum, self._epsilon, self._data_format,
            self._use_global_stats,
        )
        if self.training and self._use_global_stats is not True:
            self._mean.set_value(new_mean)
            self._variance.set_value(new_var)
        return out, new_mean, new_var

    def extra_repr(self):
        return "num_features=%d, momentum=%s, epsilon=%s" % (self._num_features, self._momentum, self._epsilon)


class BatchNorm(_BatchNormBase):
    """fluid-style BatchNorm(num_channels) alias."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5, **kwargs):
        super().__init__(num_channels, momentum, epsilon)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act == "relu":
            out = F.relu(out)
        elif self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def _check_input_dim(self, x):
        if x.ndim not in (2, 3):
            raise InvalidArgumentError("BatchNorm1D expects 2D/3D input, got %dD" % x.ndim)


class BatchNorm2D(_BatchNormBase):
    def _check_input_dim(self, x):
        if x.ndim != 4:
            raise InvalidArgumentError("BatchNorm2D expects 4D input, got %dD" % x.ndim)


class BatchNorm3D(_BatchNormBase):
    def _check_input_dim(self, x):
        if x.ndim != 5:
            raise InvalidArgumentError("BatchNorm3D expects 5D input, got %dD" % x.ndim)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BatchNorm (sync_batch_norm_op.cu parity).

    Under pjit/shard_map the batch axis is sharded; XLA computes the global
    batch statistics automatically when the reduction spans the sharded axis,
    so SyncBatchNorm == BatchNorm on TPU SPMD. Kept as a distinct class for
    API parity and for the convert_sync_batchnorm helper.
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer: Layer) -> Layer:
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            new = SyncBatchNorm(
                layer._num_features, layer._momentum, layer._epsilon,
                data_format=layer._data_format,
            )
            if layer.weight is not None:
                new.weight.set_value(layer.weight)
            if layer.bias is not None:
                new.bias.set_value(layer.bias)
            new._mean.set_value(layer._mean)
            new._variance.set_value(layer._variance)
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return layer


class GroupNorm(Layer):
    def __init__(self, num_groups: int, num_channels: int, epsilon: float = 1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.weight = (
            None if weight_attr is False
            else self.create_parameter([num_channels], attr=weight_attr, default_initializer=I.Constant(1.0))
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter([num_channels], attr=bias_attr, is_bias=True)
        )

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self.weight, self.bias, self._epsilon)


class _InstanceNormBase(Layer):
    def __init__(self, num_features: int, epsilon: float = 1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        if weight_attr is False:
            self.scale = None
            self.bias = None
        else:
            self.scale = self.create_parameter([num_features], attr=weight_attr, default_initializer=I.Constant(1.0))
            self.bias = self.create_parameter([num_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, self.scale, self.bias, self._epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta, self.k, self.data_format)


class SpectralNorm(Layer):
    """Spectral normalization layer (reference fluid/dygraph/nn.py:2994 +
    spectral_norm_op kernel semantics): forward(weight) runs ``power_iters``
    power-iteration rounds from the stored u/v vectors and returns
    weight / sigma.  u/v are registered buffers initialised ~N(0,1); the
    reference op reads them without write-back, mirrored here."""

    def __init__(self, weight_shape, dim: int = 0, power_iters: int = 1,
                 epsilon: float = 1e-12, name=None, dtype="float32"):
        super().__init__(dtype=dtype)
        if power_iters < 1:
            raise ValueError("power_iters must be a positive integer")
        self._weight_shape = [int(s) for s in weight_shape]
        self._dim = int(dim) % len(self._weight_shape)
        self._power_iters = int(power_iters)
        self._eps = float(epsilon)
        h = self._weight_shape[self._dim]
        w = 1
        for i, s in enumerate(self._weight_shape):
            if i != self._dim:
                w *= s
        self.weight_u = self.create_parameter(
            [h], dtype=dtype, default_initializer=I.Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(
            [w], dtype=dtype, default_initializer=I.Normal(0.0, 1.0))
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ..utils import _spectral_normalize

        return _spectral_normalize(
            weight, self.weight_u, self.weight_v, self._dim,
            self._power_iters, self._eps)


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned scale
    (``F.rms_norm``): the norm of the Llama/Qwen family, no mean, no
    offset."""

    def __init__(self, hidden_size: int, epsilon: float = 1e-6,
                 weight_attr=None, name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [hidden_size], attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon)

    def extra_repr(self):
        return "epsilon=%s" % self._epsilon
