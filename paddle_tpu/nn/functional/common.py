"""Common functionals (reference: python/paddle/nn/functional/common.py +
input.py + extension ops).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ...core.dtype import convert_dtype
from ...core.errors import InvalidArgumentError
from ...core.random import next_key


def linear(x, weight, bias=None):
    """paddle weight layout [in_features, out_features]."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


def rotary_embedding(x, positions, theta: float = 10000.0):
    """Rotary position embedding (Su et al. 2021) in the halves-rotated
    form the Llama/Qwen family uses: channel ``i`` pairs with ``i + D/2``
    and the pair turns by ``position * theta^(-2i/D)``.  ``x``:
    ``[B, H, L, D]``; ``positions``: ``[L]``, or ``[B, L]`` where each
    row stands at positions of its own (a slot-batched decode step).
    The angles and the turn are taken in float32; the result has ``x``'s
    type."""
    d = x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / d))
    ang = jnp.asarray(positions).astype(jnp.float32)[..., None] * inv
    ang = ang[None, None] if ang.ndim == 2 else ang[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's per-frequency blend (Peng et al. 2023, as the DeepSeek-V3
    family computes it): ``dim / 2`` inverse frequencies, numpy float32.

    Pair ``i`` turns by ``theta_i = theta^(-2i/dim)`` a position.  The
    pair that turns ``n`` times in ``original`` positions has the
    (fractional) number ``d(n) = dim * ln(original / (2 pi n)) / (2 ln
    theta)``.  Pairs up to ``low = floor(d(beta_fast))`` keep ``theta_i``
    (they turn often: extrapolated), pairs from ``high = ceil(d(
    beta_slow))`` on take ``theta_i / factor`` (interpolated), and between
    the two the blend follows the linear ramp ``(i - low) / (high -
    low)``."""
    import math

    import numpy as np

    i = np.arange(0, dim, 2, dtype=np.float32)
    extra = 1.0 / (np.float32(theta) ** (i / np.float32(dim)))
    if factor <= 1:
        return extra.astype(np.float32)

    def turn_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / span,
                   0.0, 1.0)
    return (extra / np.float32(factor) * ramp
            + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term: ``0.1 * mscale * ln(factor) +
    1`` (1 where ``factor <= 1``)."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding_pairs(x, positions, inv_freq, scale: float = 1.0):
    """Rotary positions on INTERLEAVED pairs, as the DeepSeek family
    stores them: channels ``2i`` and ``2i + 1`` are one pair, turned by
    ``position * inv_freq[i]`` (``inv_freq``: ``[D/2]``, from
    ``yarn_inv_freq`` or ``theta^(-2i/D)``).  ``x`` ``[..., L, D]`` with
    any leading dimensions; ``positions`` ``[L]``, or ``[B, L]`` where
    ``x`` is ``[B, ..., L, D]`` and every row stands at positions of its
    own.  ``scale`` multiplies cos and sin (YaRN's ``mscale`` ratio).
    Angles and the turn in float32; the result has ``x``'s type."""
    ang = jnp.asarray(positions).astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)                 # [(B,) L, D/2]
    if ang.ndim == 3:
        ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3)
                          + ang.shape[1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = xf[..., 0], xf[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def dropout(
    x,
    p: float = 0.5,
    axis=None,
    training: bool = True,
    mode: str = "upscale_in_train",
):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    if p == 1.0:
        return jnp.zeros_like(x)
    if axis is None:
        mask_shape = x.shape
    else:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        mask_shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(next_key(), 1.0 - p, mask_shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772848170429916717
    scale = 1.0507009873554804934193349852946
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(next_key(), 1.0 - p, x.shape)
    a = (1.0 / ((1.0 - p) * (1.0 + p * alpha_p**2)) ** 0.5)
    b = -a * alpha_p * p
    return (a * jnp.where(keep, x, alpha_p) + b).astype(x.dtype)


def embedding(x, weight, padding_idx: Optional[int] = None, sparse: bool = False):
    ids = x.astype(jnp.int32)
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = jnp.where(mask, out, 0.0)
    return out


def one_hot(x, num_classes: int):
    return jax.nn.one_hot(x.astype(jnp.int32), num_classes)


def label_smooth(label, prior_dist=None, epsilon: float = 0.1):
    n = label.shape[-1]
    if prior_dist is not None:
        return (1.0 - epsilon) * label + epsilon * prior_dist
    return (1.0 - epsilon) * label + epsilon / n


def pad(x, pad, mode: str = "constant", value: float = 0.0, data_format: str = "NCHW"):
    """paddle.nn.functional.pad: flat pad list is per-spatial-dim, or ndim pairs."""
    pad = list(pad)
    nd = x.ndim
    if len(pad) == 2 * nd:
        cfg = [(pad[2 * i], pad[2 * i + 1]) for i in range(nd)]
    else:
        n_spatial = len(pad) // 2
        cfg = [(0, 0)] * nd
        channel_last = data_format.endswith("C") and nd > 2
        # paddle flat pads are ordered last-spatial-first? No: [left, right,
        # top, bottom, front, back] i.e. innermost (W) first.
        spatial_axes = (
            list(range(1, 1 + n_spatial)) if channel_last else list(range(2, 2 + n_spatial))
        )
        for i, ax in enumerate(reversed(spatial_axes)):
            cfg[ax] = (pad[2 * i], pad[2 * i + 1])
    jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge", "circular": "wrap"}[mode]
    if jmode == "constant":
        return jnp.pad(x, cfg, mode="constant", constant_values=value)
    return jnp.pad(x, cfg, mode=jmode)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    """im2col (reference: operators/math/im2col) for NCHW input."""
    from .conv import _normalize_tuple

    k = _normalize_tuple(kernel_sizes, 2, "kernel_sizes")
    s = _normalize_tuple(strides, 2, "strides")
    d = _normalize_tuple(dilations, 2, "dilations")
    if isinstance(paddings, int):
        p = [(paddings, paddings)] * 2
    else:
        p = [(paddings[0], paddings[0]), (paddings[1], paddings[1])] if len(paddings) == 2 else [
            (paddings[0], paddings[2]), (paddings[1], paddings[3])
        ]
    n, c, h, w = x.shape
    xp = jnp.pad(x, [(0, 0), (0, 0), p[0], p[1]])
    oh = (xp.shape[2] - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
    ow = (xp.shape[3] - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
    patches = []
    for i in range(k[0]):
        for j in range(k[1]):
            patch = xp[:, :, i * d[0] : i * d[0] + oh * s[0] : s[0], j * d[1] : j * d[1] + ow * s[1] : s[1]]
            patches.append(patch)
    stacked = jnp.stack(patches, axis=2)  # [N, C, K*K, OH, OW]
    return stacked.reshape(n, c * k[0] * k[1], oh * ow)


def interpolate(
    x,
    size=None,
    scale_factor=None,
    mode: str = "nearest",
    align_corners: bool = False,
    align_mode: int = 0,
    data_format: str = "NCHW",
):
    if mode not in ("nearest", "linear", "bilinear", "trilinear", "area",
                    "bicubic"):
        raise InvalidArgumentError(
            "interpolate mode must be one of nearest/linear/bilinear/"
            "trilinear/bicubic/area, got %r" % (mode,))
    channel_last = data_format.endswith("C") and x.ndim > 2
    n_spatial = x.ndim - 2
    if size is None:
        if scale_factor is None:
            raise InvalidArgumentError("one of size/scale_factor is required")
        factors = (scale_factor,) * n_spatial if isinstance(scale_factor, (int, float)) else tuple(scale_factor)
        spatial = x.shape[1:-1] if channel_last else x.shape[2:]
        size = tuple(int(s * f) for s, f in zip(spatial, factors))
    else:
        size = (size,) * n_spatial if isinstance(size, int) else tuple(int(v) for v in size)
    if channel_last:
        out_shape = (x.shape[0],) + size + (x.shape[-1],)
    else:
        out_shape = (x.shape[0], x.shape[1]) + size
    spatial_axes = tuple(range(1, 1 + n_spatial)) if channel_last \
        else tuple(range(2, 2 + n_spatial))
    if mode == "nearest":
        out = x
        for ax, out_len in zip(spatial_axes, size):
            out = _resize_axis_nearest(out, ax, out_len, align_corners)
        return out
    if mode in ("linear", "bilinear", "trilinear"):
        out = x
        for ax, out_len in zip(spatial_axes, size):
            out = _resize_axis_linear(out, ax, out_len, align_corners,
                                      align_mode)
        return out
    if mode == "area":
        # reference common.py:294-300: AREA delegates to adaptive_avg_pool,
        # which averages whole input cells over integer span boundaries
        from . import pooling as _pooling
        pool = {1: _pooling.adaptive_avg_pool1d,
                2: _pooling.adaptive_avg_pool2d,
                3: _pooling.adaptive_avg_pool3d}[n_spatial]
        fmt = {1: "NLC", 2: "NHWC", 3: "NDHWC"}[n_spatial] if channel_last \
            else {1: "NCL", 2: "NCHW", 3: "NCDHW"}[n_spatial]
        return pool(x, list(size), data_format=fmt)
    # bicubic keeps the jax.image kernel (half-pixel Keys cubic; the
    # reference's bicubic uses a=-0.75 so values differ slightly)
    return jax.image.resize(x, out_shape, method="cubic")


def _resize_axis_nearest(x, axis, out_len, align_corners=False):
    in_len = x.shape[axis]
    if align_corners and out_len > 1:
        # reference align_corners nearest: round(dst * (in-1)/(out-1))
        idx = jnp.round(
            jnp.arange(out_len) * ((in_len - 1) / (out_len - 1)))
    else:
        # default convention: src = floor(dst * in/out)
        idx = jnp.floor(jnp.arange(out_len) * (in_len / out_len))
    idx = jnp.clip(idx.astype(jnp.int32), 0, in_len - 1)
    return jnp.take(x, idx, axis=axis)


def _resize_axis_linear(x, axis, out_len, align_corners, align_mode=0):
    in_len = x.shape[axis]
    if align_corners:
        # output_size 1 defines scale = 0 (select index 0, torch/paddle)
        scale = (in_len - 1) / (out_len - 1) if out_len > 1 else 0.0
        src = jnp.arange(out_len) * scale
    elif align_mode == 1:
        # paddle align_mode=1: src = dst * in/out (no half-pixel shift)
        src = jnp.arange(out_len) * (in_len / out_len)
    else:
        src = (jnp.arange(out_len) + 0.5) * (in_len / out_len) - 0.5
    i0 = jnp.clip(jnp.floor(src).astype(jnp.int32), 0, in_len - 1)
    i1 = jnp.clip(i0 + 1, 0, in_len - 1)
    w = jnp.clip(src - i0, 0.0, 1.0).astype(x.dtype)
    shape = [1] * x.ndim
    shape[axis] = out_len
    w = w.reshape(shape)
    return jnp.take(x, i0, axis=axis) * (1 - w) \
        + jnp.take(x, i1, axis=axis) * w


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW"):
    return interpolate(x, size, scale_factor, mode, align_corners, data_format=data_format)


def pixel_shuffle(x, upscale_factor: int, data_format: str = "NCHW"):
    r = upscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c // (r * r), r, r, h, w)
        x = x.transpose(0, 1, 4, 2, 5, 3)
        return x.reshape(n, c // (r * r), h * r, w * r)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, r, r, c // (r * r))
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h * r, w * r, c // (r * r))


def bilinear(x1, x2, weight, bias=None):
    # weight: [out, in1, in2]
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p: float = 0.0, is_causal: bool = False, training: bool = True
):
    """Batched attention: [B, H, L, D] layout.

    Routed to the pallas flash kernel (``paddle_tpu.ops.flash_attention``)
    when backend/shape allow — including transparently recognizing a
    materialized 2-D causal additive mask so paddle-style callers get the
    kernel's causal fast path.  Falls back to the XLA composition (which XLA
    still fuses, but with the [L, L] scores in HBM).
    """
    from ...ops.flash_attention import (
        detect_causal_additive_mask,
        detect_padding_additive_mask,
        flash_attention,
        flash_attention_supported,
    )

    d = query.shape[-1]
    drop_p = dropout_p if training else 0.0
    if flash_attention_supported(query.shape, query.dtype, drop_p) \
            and flash_attention_supported(key.shape, key.dtype, drop_p) \
            and tuple(key.shape) == tuple(value.shape) \
            and tuple(query.shape[:2]) == tuple(key.shape[:2]) \
            and (attn_mask is None or attn_mask.dtype != jnp.bool_):
        mask = attn_mask
        causal = is_causal
        if not causal and detect_causal_additive_mask(mask, query.shape[-2]):
            causal, mask = True, None
        key_mask = None
        if mask is not None:
            pad_valid = detect_padding_additive_mask(mask)
            if pad_valid is not None and \
                    pad_valid.shape[-1] == key.shape[-2]:
                key_mask, mask = jnp.asarray(pad_valid), None
        return flash_attention(query, key, value, bias=mask, causal=causal,
                               key_padding_mask=key_mask)
    scores = jnp.einsum("...qd,...kd->...qk", query, key) / jnp.sqrt(d).astype(query.dtype)
    if is_causal:
        q_len, k_len = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((q_len, k_len), dtype=bool))
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, jnp.finfo(scores.dtype).min)
        else:
            scores = scores + attn_mask
    weights = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and training:
        weights = dropout(weights, dropout_p, training=training)
    return jnp.einsum("...qk,...kd->...qd", weights, value)


def sequence_mask(lengths, maxlen: Optional[int] = None, dtype="int64"):
    """Delegates to ``tensor.segment.sequence_mask`` (single implementation;
    the int64 default is this API's paddle-parity surface)."""
    from ...tensor.segment import sequence_mask as _impl

    return _impl(lengths, maxlen=maxlen, dtype=dtype)


def temporal_shift(x, seg_num: int, shift_ratio: float = 0.25, data_format: str = "NCHW"):
    nt, c, h, w = x.shape
    n = nt // seg_num
    x = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    out = jnp.zeros_like(x)
    out = out.at[:, :-1, :fold].set(x[:, 1:, :fold])
    out = out.at[:, 1:, fold : 2 * fold].set(x[:, :-1, fold : 2 * fold])
    out = out.at[:, :, 2 * fold :].set(x[:, :, 2 * fold :])
    return out.reshape(nt, c, h, w)


def diag_embed(input, offset: int = 0, dim1: int = -2, dim2: int = -1):
    """nn.functional diag_embed parity: last axis becomes the (offset)
    diagonal of a new matrix spanned by dim1/dim2."""
    x = jnp.asarray(input)
    n = x.shape[-1]
    size = n + abs(offset)
    rows = jnp.arange(n) + max(-offset, 0)
    cols = jnp.arange(n) + max(offset, 0)
    out = jnp.zeros(x.shape[:-1] + (size, size), x.dtype)
    out = out.at[..., rows, cols].set(x)
    nd = out.ndim
    d1 = dim1 % nd
    d2 = dim2 % nd
    if (d1, d2) != (nd - 2, nd - 1):
        out = jnp.moveaxis(out, (nd - 2, nd - 1), (d1, d2))
    return out


def gather_tree(ids, parents):
    """gather_tree_op parity: back-trace beam-search parent pointers.

    ids/parents: [max_time, batch, beam] — returns the full sequences
    reconstructed from the last step's beams.
    """
    from jax import lax

    ids = jnp.asarray(ids)
    parents = jnp.asarray(parents).astype(jnp.int32)
    T, B, K = ids.shape

    def step(beam_ptr, t):
        # beam_ptr [B, K]: which original beam each final slot follows at t+1
        idx = beam_ptr
        tok = jnp.take_along_axis(ids[t], idx, axis=1)
        prev = jnp.take_along_axis(parents[t], idx, axis=1)
        return prev, tok

    init = jnp.tile(jnp.arange(K)[None, :], (B, 1))
    _, toks = lax.scan(step, init, jnp.arange(T - 1, -1, -1))
    return jnp.flip(toks, axis=0)
