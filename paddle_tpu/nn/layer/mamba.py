"""The Mamba-1 token mixer as the Jamba family runs it (``ops/selective_scan
.py`` has the recurrence and the state's layout).

On a layer's normed input ``x [L, H]``::

    [u, z]    = x W_in                                each [L, C], C = expand * H
    c[t]      = silu(b_c + sum_j w_c[j] * u[t - (K-1) + j])       depthwise,
                                                      causal, kernel K (u[<0] = 0)
    [r, B, C] = c W_x             [L, dt_rank], [L, N], [L, N]; each through an
                                  RMSNorm of its own (the Jamba family's addition)
    dt        = softplus(r W_dt + b_dt)               [L, C], float32
    s[t]      = exp(dt[t] (x) A) * s[t-1] + (dt[t] * c[t]) (x) B[t]     A = -exp(A_log)
    y[t]      = s[t] . C[t] + D * c[t]
    out       = (y * silu(z)) W_out

Its decode cache is :data:`MambaDecodeCache`, one a layer: ``conv`` ``[B,
(K-1) * C]`` (the last ``K-1`` inputs ``u`` of the convolution side by side,
oldest first, in the activations' type: as ``[B, K-1, C]`` the chip's (16,
128) tiles of a bfloat16 array would pad 3 rows to 16, in memory and in
every step's read and write) and ``ssm`` ``[B, N, C]`` float32 (channels
innermost), ``index`` (positions consumed: a scalar, or ``[B]`` in a pool)
and ``limit`` (a position at or past it is an identity step: a padded
bucket's tail in the prefill, a free slot's row in a pool's step).  ``jit.cache.RecurrentLayout``
places, splices, freezes and spills it with the other recurrent caches.

An identity step of ``ssm`` is ``dt = 0``, no select (``exp(0) = 1``, and
``0 * c * B`` is added); ``conv`` is read at the true length: the inputs
``[n, n + K - 1)`` of the old state followed by the chunk's, ``n`` the
positions of the chunk inside the window.

``A_log``, ``D``, ``b_dt`` and the three norms' scales are float32 whatever
the layer's type: ``exp(dt * A)`` over thousands of positions is what the
state remembers by.  ``A_log`` is stored ``[N, C]``, the published tensor
transposed.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Tensor
from ...ops import selective_scan as ops
from .. import initializer as I
from .common import Linear
from .layers import Layer
from .norm import RMSNorm

__all__ = ["MambaDecodeCache", "MambaMixer"]

MambaDecodeCache = collections.namedtuple(
    "MambaDecodeCache", ["conv", "ssm", "index", "limit"])


class MambaMixer(Layer):
    """``forward(x)`` runs a whole sequence from an empty state;
    ``forward(x, cache=...)`` continues from the cache: one position is the
    decode step, more a prefill chunk."""

    def __init__(self, hidden_size: int, d_inner: int, d_state: int = 16,
                 d_conv: int = 4, dt_rank: int = 160,
                 norm_epsilon: float = 1e-6):
        super().__init__()
        self.hidden_size, self.d_inner = int(hidden_size), int(d_inner)
        self.d_state, self.d_conv = int(d_state), int(d_conv)
        self.dt_rank = int(dt_rank)
        if self.d_conv < 2:
            raise InvalidArgumentError(
                "the convolution keeps its last d_conv - 1 inputs: d_conv "
                "%d leaves no state" % d_conv)
        self.in_proj = Linear(hidden_size, 2 * d_inner, bias_attr=False)
        self.conv_weight = self.create_parameter(
            [d_conv, d_inner], default_initializer=I.Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter(
            [d_inner], is_bias=True)
        self.x_proj = Linear(d_inner, dt_rank + 2 * d_state, bias_attr=False)
        self.dt_proj = Linear(dt_rank, d_inner, bias_attr=False)
        f32 = dict(dtype="float32")
        self.dt_bias = self.create_parameter([d_inner], is_bias=True, **f32)
        self.A_log = self.create_parameter(
            [d_state, d_inner], default_initializer=I.Constant(0.0), **f32)
        self.D = self.create_parameter(
            [d_inner], default_initializer=I.Constant(1.0), **f32)
        self.out_proj = Linear(d_inner, hidden_size, bias_attr=False)
        # the norms' scales in float32 (F.rms_norm gives the input's type
        # back)
        self.dt_norm, self.b_norm, self.c_norm = (
            _f32_norm(n, norm_epsilon)
            for n in (dt_rank, d_state, d_state))

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "recurrent", block_size: int = 32,
                         num_blocks=None):
        """The state is this layer's whatever ``layout`` the model's
        attention layers take; ``dtype`` is theirs too: ``conv`` is held in
        the layer's own type and ``ssm`` in float32."""
        return MambaDecodeCache(
            conv=jnp.zeros((batch_size, (self.d_conv - 1) * self.d_inner),
                           self.in_proj.weight.value.dtype),
            ssm=jnp.zeros((batch_size, self.d_state, self.d_inner),
                          jnp.float32),
            index=(jnp.zeros((batch_size,), jnp.int32) if per_slot
                   else jnp.asarray(0, jnp.int32)),
            limit=jnp.asarray(int(max_length), jnp.int32))

    def _conv(self, taps):
        """``silu`` of the causal depthwise convolution as ``d_conv``
        shifted products: ``taps[j]`` is the input ``d_conv - 1 - j``
        positions back, ``[..., C]``."""
        w = self.conv_weight.value.astype(jnp.float32)
        acc = self.conv_bias.value.astype(jnp.float32)
        for j, tap in enumerate(taps):
            acc = acc + w[j] * tap.astype(jnp.float32)
        return jax.nn.silu(acc).astype(taps[-1].dtype)

    def forward(self, x, cache=None):
        b, length = x.shape[0], x.shape[1]
        k1 = self.d_conv - 1
        with jax.named_scope("mamba/in_proj"):
            uz = self.in_proj(x).value
            u, z = uz[..., :self.d_inner], uz[..., self.d_inner:]
        c_ = self.d_inner
        if cache is None:
            conv_state = jnp.zeros((b, k1 * c_), u.dtype)
            ssm = jnp.zeros((b, self.d_state, c_), jnp.float32)
            keep = None
        else:
            conv_state, ssm = cache.conv, cache.ssm
            idx = jnp.asarray(cache.index, jnp.int32)
            pos = idx[..., None] + jnp.arange(length, dtype=jnp.int32)
            keep = jnp.broadcast_to(pos, (b, length)) \
                < jnp.reshape(cache.limit, (-1, 1))
        with jax.named_scope("mamba/conv"):
            if cache is not None and length == 1:
                # the step: the state's inputs are whole lane-aligned
                # slices, and the new state drops the oldest of them
                taps = [conv_state[:, j * c_:(j + 1) * c_]
                        for j in range(k1)] + [u[:, 0]]
                c = self._conv(taps)[:, None]
                conv_state = jnp.where(
                    keep, jnp.concatenate(taps[1:], axis=-1)
                    .astype(conv_state.dtype), conv_state)
            else:
                window = jnp.concatenate(
                    [conv_state.reshape(b, k1, c_).astype(u.dtype), u],
                    axis=1)
                c = self._conv([window[:, j:j + length]
                                for j in range(self.d_conv)])
                if keep is not None:
                    # the state of the TRUE length: the window's rows [n,
                    # n + K - 1), n the chunk's positions inside the
                    # update window
                    n = jnp.sum(keep, axis=1, dtype=jnp.int32)
                    at = n[:, None] + jnp.arange(k1, dtype=jnp.int32)
                    conv_state = jnp.take_along_axis(
                        window, at[:, :, None], axis=1) \
                        .reshape(b, k1 * c_).astype(conv_state.dtype)
        with jax.named_scope("mamba/x_proj"):
            rbc = self.x_proj(Tensor(c, stop_gradient=True))
            r = self.dt_norm(rbc[..., :self.dt_rank]).value
            bm = self.b_norm(
                rbc[..., self.dt_rank:self.dt_rank + self.d_state]).value
            cm = self.c_norm(rbc[..., self.dt_rank + self.d_state:]).value
            dt = jax.nn.softplus(
                jnp.matmul(r, self.dt_proj.weight.value,
                           preferred_element_type=jnp.float32)
                + self.dt_bias.value)
            if keep is not None:
                dt = jnp.where(keep[:, :, None], dt, 0.0)
            bm, cm = bm.astype(jnp.float32), cm.astype(jnp.float32)
            a = -jnp.exp(self.A_log.value)
        d = self.D.value
        if cache is not None and length == 1:
            with jax.named_scope("mamba/scan_step"):
                y, ssm = ops.selective_scan_step(
                    dt[:, 0], c[:, 0], bm[:, 0], cm[:, 0], a, d, ssm)
                y = y[:, None]
        else:
            with jax.named_scope("mamba/scan_chunk"):
                y, ssm = ops.selective_scan_prefill(dt, c, bm, cm, a, d, ssm)
        with jax.named_scope("mamba/out_proj"):
            gated = y.astype(z.dtype) * jax.nn.silu(z)
            out = self.out_proj(Tensor(gated, stop_gradient=True))
        if cache is None:
            return out
        return out, cache._replace(conv=conv_state, ssm=ssm,
                                   index=idx + jnp.int32(length))


def _f32_norm(size: int, epsilon: float) -> RMSNorm:
    from ...core.dtype import get_default_dtype, set_default_dtype

    was = get_default_dtype()
    set_default_dtype("float32")
    try:
        return RMSNorm(size, epsilon)
    finally:
        set_default_dtype(was)
