"""Activation functionals (reference: python/paddle/nn/functional/activation.py,
C++ kernels in paddle/fluid/operators/activation_op.*).

Raw-array impls over jax.nn/jnp; XLA fuses these into adjacent matmuls on TPU
so there is no per-op kernel to hand-write.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jnp.minimum(jnp.maximum(x, 0), 6)


def relu_(x):
    return jax.nn.relu(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def gelu(x, approximate: bool = False):
    return jax.nn.gelu(x, approximate=approximate)


def leaky_relu(x, negative_slope: float = 0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def elu(x, alpha: float = 1.0):
    return jax.nn.elu(x, alpha)


def selu(
    x,
    scale: float = 1.0507009873554804934193349852946,
    alpha: float = 1.6732632423543772848170429916717,
):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def silu(x):
    return jax.nn.silu(x)


def swish(x):
    return jax.nn.silu(x)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def softplus(x, beta: float = 1.0, threshold: float = 20.0):
    bx = beta * x
    return jnp.where(bx > threshold, x, jnp.logaddexp(bx, 0.0) / beta)


def softsign(x):
    return jax.nn.soft_sign(x)


def softshrink(x, threshold: float = 0.5):
    return jnp.where(x > threshold, x - threshold, jnp.where(x < -threshold, x + threshold, 0.0))


def hardshrink(x, threshold: float = 0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def hardtanh(x, min: float = -1.0, max: float = 1.0):
    return jnp.clip(x, min, max)


def hardsigmoid(x, slope: float = 0.1666667, offset: float = 0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def tanhshrink(x):
    return x - jnp.tanh(x)


def thresholded_relu(x, threshold: float = 1.0):
    return jnp.where(x > threshold, x, 0.0)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def maxout(x, groups: int, axis: int = 1):
    shape = list(x.shape)
    axis = axis % x.ndim
    shape[axis] = shape[axis] // groups
    shape.insert(axis + 1, groups)
    return jnp.max(jnp.reshape(x, shape), axis=axis + 1)


def prelu(x, weight):
    w = weight
    if w.ndim == 1 and w.shape[0] > 1 and x.ndim > 2:
        # per-channel weight broadcasts over NCHW channel axis
        w = w.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x > 0, x, w * x)


def softmax(x, axis: int = -1, dtype=None):
    if dtype is not None:
        x = x.astype(dtype)
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis: int = -1, dtype=None):
    if dtype is not None:
        x = x.astype(dtype)
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, temperature: float = 1.0, hard: bool = False, axis: int = -1):
    from ...core.random import next_key

    g = jax.random.gumbel(next_key(), x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        hard_y = jnp.zeros_like(y)
        hard_y = jnp.put_along_axis(hard_y, idx, 1.0, axis=axis,
                                    inplace=False)
        y = jax.lax.stop_gradient(hard_y - y) + y
    return y


def glu(x, axis: int = -1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)
