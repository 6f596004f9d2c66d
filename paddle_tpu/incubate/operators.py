"""Incubate fused operators (``paddle.incubate.operators``).

Reference: ``python/paddle/incubate/operators/`` — CUDA-fused kernels
behind simple python entry points. On TPU the fusion itself belongs to
XLA: these are expressed as plain traced ops (mask-add + softmax) that
XLA fuses into one kernel, so the API survives while the hand-fused
CUDA op dissolves (``softmax_mask_fuse_upper_triangle.py:33``,
``softmax_mask_fuse.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.errors import InvalidArgumentError
from ..framework.dispatch import make_op

__all__ = ["softmax_mask_fuse", "softmax_mask_fuse_upper_triangle"]


def _softmax_mask_fuse_upper_triangle(x):
    """Causal-masked softmax over the last axis of ``[B, H, Lq, Lk]``
    attention scores — the GPT pattern, no mask tensor needed. Strictly
    upper-triangle positions (future keys) get zero probability; each
    softmax row normalizes over the keys it may attend to. ``Lk >= Lq``
    (KV-cache style offsets allowed; the reference op is square-only)."""
    if x.ndim != 4:
        raise InvalidArgumentError(
            "softmax_mask_fuse_upper_triangle expects [B, H, Lq, Lk], "
            "got rank %d" % x.ndim)
    lq, lk = x.shape[-2], x.shape[-1]
    if lq > lk:
        raise InvalidArgumentError(
            "softmax_mask_fuse_upper_triangle needs Lk >= Lq (got Lq=%d, "
            "Lk=%d): rows past the key length would attend to nothing"
            % (lq, lk))
    keep = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
    # where= keeps masked lanes out of the reduction and zeroes them in
    # the output (with Lk >= Lq every row has at least one kept key, so
    # the max is well-defined)
    return jax.nn.softmax(x, axis=-1, where=keep).astype(x.dtype)


def _softmax_mask_fuse(x, mask):
    """Softmax over ``x + mask`` (additive attention mask) on the last
    axis — the non-causal sibling; XLA fuses the add into the softmax."""
    return jax.nn.softmax(x + mask, axis=-1).astype(x.dtype)


softmax_mask_fuse_upper_triangle = make_op(
    _softmax_mask_fuse_upper_triangle,
    op_name="softmax_mask_fuse_upper_triangle")
softmax_mask_fuse = make_op(_softmax_mask_fuse, op_name="softmax_mask_fuse")
