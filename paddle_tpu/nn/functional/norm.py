"""Normalization functionals (reference: python/paddle/nn/functional/norm.py;
kernels layer_norm_op.cc:291, batch_norm_op.cc).

``batch_norm`` is pure: it returns (out, new_mean, new_var) so both eager
layers (which write the stats back into buffers) and jit-functionalized
training (which threads them as state) share one implementation — the
TPU-native replacement for the reference's in-place running-stat mutation.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ...core.errors import InvalidArgumentError


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon: float = 1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax_rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def jax_rsqrt(v):
    return jnp.reciprocal(jnp.sqrt(v))


def rms_norm(x, weight=None, epsilon: float = 1e-6):
    """Root-mean-square norm over the last axis (Zhang & Sennrich 2019):
    ``x / sqrt(mean(x^2) + epsilon) * weight``, no mean taken off and no
    offset.  The statistic is taken in float32 whatever ``x`` is stored
    in, and the result goes back to ``x``'s type before the learned
    scale, as the Llama/Qwen family's layers do.  A scale kept in a wider
    type than ``x`` (float32 under bfloat16 activations) multiplies in its
    own type and the product goes back to ``x``'s."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = (xf * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    return out if weight is None else (out * weight).astype(x.dtype)


def batch_norm_stats(x, data_format: str = "NCHW"):
    axes = _reduce_axes(x, data_format)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    return mean, var


def _reduce_axes(x, data_format):
    if data_format.endswith("C") and x.ndim > 2:
        return tuple(i for i in range(x.ndim) if i != x.ndim - 1)
    return tuple(i for i in range(x.ndim) if i != 1) if x.ndim > 1 else (0,)


def _channel_shape(x, data_format):
    if data_format.endswith("C") and x.ndim > 2:
        return (1,) * (x.ndim - 1) + (-1,)
    if x.ndim > 1:
        return (1, -1) + (1,) * (x.ndim - 2)
    return (-1,)


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training: bool = False,
    momentum: float = 0.9,
    epsilon: float = 1e-5,
    data_format: str = "NCHW",
    use_global_stats: Optional[bool] = None,
):
    """Returns (out, new_running_mean, new_running_var)."""
    use_batch_stats = training and not use_global_stats
    if use_batch_stats:
        mean, var = batch_norm_stats(x, data_format)
        new_mean = momentum * running_mean + (1.0 - momentum) * mean
        new_var = momentum * running_var + (1.0 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    cshape = _channel_shape(x, data_format)
    out = (x - mean.reshape(cshape)) * jax_rsqrt(var.reshape(cshape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(cshape)
    if bias is not None:
        out = out + bias.reshape(cshape)
    return out, new_mean, new_var


def instance_norm(x, weight=None, bias=None, eps: float = 1e-5, data_format: str = "NCHW"):
    if data_format != "NCHW" and not data_format.startswith("NC"):
        raise InvalidArgumentError("instance_norm supports channel-first layouts only")
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax_rsqrt(var + eps)
    cshape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(cshape)
    if bias is not None:
        out = out + bias.reshape(cshape)
    return out


def group_norm(x, num_groups: int, weight=None, bias=None, epsilon: float = 1e-5, data_format: str = "NCHW"):
    if not data_format.startswith("NC"):
        raise InvalidArgumentError("group_norm supports channel-first layouts only")
    n, c = x.shape[0], x.shape[1]
    if c % num_groups != 0:
        raise InvalidArgumentError("channels %d not divisible by num_groups %d" % (c, num_groups))
    orig_shape = x.shape
    g = x.reshape((n, num_groups, c // num_groups) + x.shape[2:])
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    out = ((g - mean) * jax_rsqrt(var + epsilon)).reshape(orig_shape)
    cshape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(cshape)
    if bias is not None:
        out = out + bias.reshape(cshape)
    return out


def local_response_norm(x, size: int, alpha: float = 1e-4, beta: float = 0.75, k: float = 1.0, data_format: str = "NCHW"):
    sq = jnp.square(x)
    half = size // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[1] = (half, size - half - 1)
    padded = jnp.pad(sq, pad_cfg)
    windows = sum(
        jnp.take(padded, jnp.arange(i, i + x.shape[1]), axis=1) for i in range(size)
    )
    # reference (and torch) average the window: alpha scales sum/size
    return x / jnp.power(k + alpha * windows / size, beta)


def normalize(x, p: float = 2, axis: int = 1, epsilon: float = 1e-12):
    norm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p)
    return x / jnp.maximum(norm, epsilon)
