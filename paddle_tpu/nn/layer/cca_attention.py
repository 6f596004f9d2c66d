"""Compressed convolutional attention (CCA, arXiv:2510.04476; the ZAYA1
family): grouped-head softmax attention whose queries and keys are MIXED
ALONG THE SEQUENCE before the softmax, by two short causal convolutions, and
whose values come from two tokens.

On a layer's normed input ``a [T, H]``, ``n`` query heads on ``n_kv`` K/V
heads of ``d`` channels (``G = n / n_kv``), convolutions of ``k0`` and ``k1``
taps::

    u           = a W_qk                  [T, (n + n_kv) d]: the latents q~ (its
                                          first n heads) and k~ (the rest)
    c0[t, c]    = b0[c] + sum_j w0[j, c] u[t - (k0-1) + j, c]           depthwise
    c1[t, g, o] = b1[g, o] + sum_j sum_i w1[j, g, i, o] c0[t - (k1-1) + j, g, i]
                                          grouped by head g (n + n_kv groups of d);
                                          both causal, zeros before position 0
    m_i  = (q~_i + k~_{i // G}) / 2;  mbar_j = mean of m_i over the G heads of group j
    q_i  = c1's head i + m_i;  k_j = c1's head n + j + mbar_j
    q_i  = sqrt(d) q_i / |q_i|;  k_j = sqrt(d) k_j / |k_j| * temp_j       float32
    q, k = rotary positions on the first ``rotary_dim`` channels of a head
    v[t] = [a[t] W_v's first half, a[t-1] W_v's second half]   as n_kv heads of d:
                                          the first n_kv / 2 heads hold this
                                          token's values, the rest the token
                                          before's (zeros before position 0)
    o_i[t] = sum_{s<=t} softmax_s(q_i[t] . k_{i//G}[s] / sqrt(d)) v_{i//G}[s]
    out  = concat_i(o_i) W_o

**A layer owns TWO cache entries**, and ``gen_decode_cache`` returns both:
the K/V entry every grouped-head layer keeps (dense or paged: the mixed,
normed and turned ``k`` and the two-token ``v`` of a position), and a
:data:`CCADecodeCache`, a state of constant size in ``jit.cache
.RecurrentLayout``'s discipline: what the NEXT position's taps and values
read of the positions before it: ``u`` ``[B, (k0-1) * W]`` (the last
``k0 - 1`` latents, oldest first, ``W = (n + n_kv) d``), ``c0`` ``[B, (k1-1)
* W]`` (the first convolution's last outputs) and ``v_next`` ``[B, n_kv d /
2]`` (``a[t] W_v``'s second half); flat, as ``nn.MambaDecodeCache``'s
``conv``, so that no tile of the chip pads a short axis.  ``limit`` is the
update window: a position at or past it leaves the state as it was (a
padded bucket's tail: the state is the TRUE last position's; a free slot's
row in a pool's step: its state comes through to the bit).

**Three paths, one computation.**  The taps read a window made of the state
and the chunk; with no cache the state is zeros.  A chunk KNOWN to start at
position 0 while the program is traced (no cache, or a cache made in the
same trace, as the bucketed prefill makes it) attends over its own keys
(``ops.flash_attention.causal_attention``: the flash kernel where it runs);
every other chunk (a decode step, a chunk that starts mid-way) attends the
cache it has just been written to, through the grouped-head ops every other
layer uses (``paged_decode_attention`` / ``decode_attention``: the fused
kernel for a short chunk).
"""
from __future__ import annotations

import collections
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from .common import Linear
from .latent_attention import _starts_at_zero
from .layers import Layer
from .transformer import MultiHeadAttention

__all__ = ["CCADecodeCache", "CCAttention"]

CCADecodeCache = collections.namedtuple(
    "CCADecodeCache", ["u", "c0", "v_next", "index", "limit"])


def _window(state, chunk, taps: int):
    """``[B, taps - 1 + L, W]``: the state's ``taps - 1`` rows (oldest
    first), then the chunk's."""
    b, _, w = chunk.shape
    return jnp.concatenate(
        [state.reshape(b, taps - 1, w).astype(chunk.dtype), chunk], axis=1)


def _state_after(window, n, rows: int, old):
    """The ``rows`` rows of ``window`` that end at its row ``n + rows - 1``:
    the state after ``n`` (``[B]``) positions of the chunk, flat, in
    ``old``'s type.  A step (a window of ``rows + 1``) selects instead of
    gathering: ``n`` is 0 or 1."""
    b, total, w = window.shape
    if total == rows + 1:
        new = window[:, 1:].reshape(b, rows * w).astype(old.dtype)
        return jnp.where((n > 0)[:, None], new, old)
    at = n[:, None] + jnp.arange(rows, dtype=jnp.int32)
    return jnp.take_along_axis(window, at[:, :, None], axis=1) \
        .reshape(b, rows * w).astype(old.dtype)


class CCAttention(Layer):
    """See the module docstring.  ``forward(a)`` runs a whole sequence from
    position 0; ``forward(a, cache=(kv, state))`` continues from the layer's
    two entries and returns ``(out, (kv, state))``."""

    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, conv_taps=(2, 2), rope_theta: float = 10000.0,
                 rotary_dim: Optional[int] = None):
        super().__init__()
        if num_kv_heads < 2 or num_kv_heads % 2 or num_heads % num_kv_heads:
            raise InvalidArgumentError(
                "num_heads %d on num_kv_heads %d: the K/V heads are an even "
                "number (half hold a token's own values, half the token "
                "before's) that divides the query heads"
                % (num_heads, num_kv_heads))
        rotary_dim = head_dim if rotary_dim is None else int(rotary_dim)
        if rotary_dim % 2 or not 0 < rotary_dim <= head_dim:
            raise InvalidArgumentError(
                "rotary positions turn pairs of a head's first channels: "
                "rotary_dim %d of head_dim %d" % (rotary_dim, head_dim))
        if len(conv_taps) != 2 or min(conv_taps) < 2:
            raise InvalidArgumentError(
                "conv_taps=%r: two convolutions of at least 2 taps each (one "
                "tap mixes nothing and keeps no state)" % (conv_taps,))
        self.hidden_size = int(hidden_size)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim, self.rotary_dim = int(head_dim), rotary_dim
        self.conv_taps = (int(conv_taps[0]), int(conv_taps[1]))
        self.rope_theta = float(rope_theta)
        n, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        #: channels of the latents ``u``: the query heads', then the K/V
        #: heads'
        self.width = (n + nkv) * d
        k0, k1 = self.conv_taps
        self.qk_down = Linear(hidden_size, self.width, bias_attr=False)
        self.v_proj = Linear(hidden_size, nkv * d, bias_attr=False)
        self.o_proj = Linear(n * d, hidden_size, bias_attr=False)
        self.conv0_weight = self.create_parameter(
            [k0, self.width],
            default_initializer=I.Normal(0.0, 1.0 / math.sqrt(k0)))
        self.conv0_bias = self.create_parameter([self.width], is_bias=True)
        self.conv1_weight = self.create_parameter(
            [k1, n + nkv, d, d],
            default_initializer=I.Normal(0.0, 1.0 / math.sqrt(k1 * d)))
        self.conv1_bias = self.create_parameter([self.width], is_bias=True)
        self.temp = self.create_parameter(
            [nkv], default_initializer=I.Constant(1.0))

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """The layer's two entries, ``(kv, state)``: K/V in ``layout`` and
        ``dtype`` as ``MultiHeadAttention.gen_decode_cache`` builds it for
        ``num_kv_heads`` heads, and the :data:`CCADecodeCache` in the
        layer's own type.  A single sequence's state ``index`` is a numpy
        zero: made inside a trace (the bucketed prefill) it stays a
        constant that ``forward`` can read."""
        if jnp.dtype(dtype) == jnp.int8:
            raise InvalidArgumentError(
                "CCAttention keeps a float K/V cache: the int8 cache has no "
                "grouped-head attention")
        kv = MultiHeadAttention.gen_decode_cache(
            self, batch_size, max_length, dtype, per_slot, layout,
            block_size, num_blocks)
        index = jnp.zeros((batch_size,), jnp.int32) if per_slot \
            else np.zeros((), np.int32)
        return kv, self._empty_state(batch_size, index,
                                     jnp.asarray(int(max_length), jnp.int32))

    def _empty_state(self, batch_size: int, index, limit) -> CCADecodeCache:
        """The state before position 0: zeros, in the layer's own type."""
        k0, k1 = self.conv_taps
        zeros = lambda w: jnp.zeros((batch_size, w),
                                    self.qk_down.weight.value.dtype)
        return CCADecodeCache(
            zeros((k0 - 1) * self.width), zeros((k1 - 1) * self.width),
            zeros(self.num_kv_heads * self.head_dim // 2), index, limit)

    # ``MultiHeadAttention.gen_decode_cache`` reads these of its layer
    DecodeCache = MultiHeadAttention.DecodeCache
    PagedDecodeCache = MultiHeadAttention.PagedDecodeCache

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def _mix(self, u, c1):
        """q ``[B, n, L, d]`` and k ``[B, n_kv, L, d]`` from the latents and
        their convolution: the skip through the means, the norm, ``temp``.
        Float32 throughout."""
        b, length = u.shape[0], u.shape[1]
        n, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        uf = u.astype(jnp.float32).reshape(b, length, n + nkv, d)
        cf = c1.reshape(b, length, n + nkv, d)
        q_lat = uf[:, :, :n].reshape(b, length, nkv, n // nkv, d)
        k_lat = uf[:, :, n:]
        m = (q_lat + k_lat[:, :, :, None]) * 0.5
        q = cf[:, :, :n] + m.reshape(b, length, n, d)
        k = cf[:, :, n:] + jnp.mean(m, axis=3)

        def unit(x):
            return x * (math.sqrt(d) * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True)))

        k = unit(k) * self.temp.value.astype(jnp.float32)[:, None]
        return jnp.swapaxes(unit(q), 1, 2), jnp.swapaxes(k, 1, 2)

    def _rope(self, x, pos):
        r = self.rotary_dim
        turned = F.rotary_embedding(x[..., :r], pos, self.rope_theta)
        return turned if r == self.head_dim else \
            jnp.concatenate([turned, x[..., r:]], axis=-1)

    def _write(self, kv, k, v, pos):
        """The chunk's ``k`` and ``v`` ``[B, n_kv, L, d]`` into the K/V
        entry at ``pos`` ``[B, L]``."""
        from ...ops.flash_attention import paged_kv_write

        b = k.shape[0]
        rows = jnp.arange(b)[:, None]
        if isinstance(kv, self.DecodeCache):
            with jax.named_scope("cache_write"):
                put = lambda buf, new: buf.at[rows, :, pos].set(
                    jnp.swapaxes(new, 1, 2).astype(buf.dtype), mode="drop")
                return kv._replace(k=put(kv.k, k), v=put(kv.v, v))
        table = jnp.asarray(kv.table, jnp.int32)
        bs = kv.k.shape[2]
        # a position past the table's span goes to the scratch block, as
        # every paged write routes it
        logical = jnp.minimum(pos // bs, table.shape[1] - 1)
        phys = jnp.where(pos < table.shape[1] * bs, table[rows, logical], 0)
        k_pool, v_pool = paged_kv_write(kv.k, kv.v, k, v, phys, pos % bs)
        return kv._replace(k=k_pool, v=v_pool)

    def forward(self, a, cache=None):
        from ...ops.flash_attention import (causal_attention,
                                            decode_attention,
                                            paged_decode_attention)

        b, length = a.shape[0], a.shape[1]
        n, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        k0, k1 = self.conv_taps
        half = nkv * d // 2
        with jax.named_scope("cca/qk_down"):
            u = self.qk_down(a).value
        with jax.named_scope("cca/value"):
            vv = self.v_proj(a).value
        steps = jnp.arange(length, dtype=jnp.int32)
        if cache is None:
            kv = None
            state = self._empty_state(b, np.zeros((), np.int32), None)
            pos = jnp.broadcast_to(steps, (b, length))
            seen = jnp.full((b,), length, jnp.int32)
        else:
            kv, state = cache
            idx = jnp.asarray(state.index, jnp.int32)
            pos = jnp.broadcast_to(idx[..., None] + steps, (b, length))
            # the chunk's positions inside the update window
            seen = jnp.sum(pos < jnp.reshape(state.limit, (-1, 1)), axis=1,
                           dtype=jnp.int32)
        with jax.named_scope("cca/mix"):
            f32 = jnp.float32
            win_u = _window(state.u, u, k0)
            c0 = self.conv0_bias.value.astype(f32)
            for j in range(k0):
                c0 = c0 + self.conv0_weight.value[j].astype(f32) \
                    * win_u[:, j:j + length].astype(f32)
            c0 = c0.astype(u.dtype)
            win_c = _window(state.c0, c0, k1)
            c1 = self.conv1_bias.value.astype(f32)
            for j in range(k1):
                c1 = c1 + jnp.einsum(
                    "blgi,gio->blgo",
                    win_c[:, j:j + length].reshape(b, length, n + nkv, d),
                    self.conv1_weight.value[j],
                    preferred_element_type=f32).reshape(b, length, -1)
            q, k = self._mix(u, c1)
        with jax.named_scope("rope"):
            q = self._rope(q, pos).astype(u.dtype)
            k = self._rope(k, pos).astype(u.dtype)
        with jax.named_scope("cca/value"):
            win_v = _window(state.v_next, vv[..., half:], 2)
            v = jnp.concatenate([vv[..., :half], win_v[:, :length]], axis=-1)
            v = jnp.swapaxes(v.reshape(b, length, nkv, d), 1, 2)
        if kv is not None:
            kv = self._write(kv, k, v, pos)
        if kv is None or _starts_at_zero(state.index):
            # a prompt from position 0, over its own keys
            with jax.named_scope("prefill_attn"):
                o = causal_attention(q, jnp.repeat(k, n // nkv, axis=1),
                                     jnp.repeat(v, n // nkv, axis=1),
                                     1.0 / math.sqrt(d))
        elif isinstance(kv, self.DecodeCache):
            o = decode_attention(q, kv.k, kv.v, q_pos=pos)
        else:
            o = paged_decode_attention(q, kv.k, kv.v, kv.table, q_pos=pos)
        with jax.named_scope("cca/o_proj"):
            out = self.o_proj(Tensor(
                jnp.swapaxes(o, 1, 2).reshape(b, length, n * d)
                .astype(u.dtype), stop_gradient=True))
        if cache is None:
            return out
        state = state._replace(
            u=_state_after(win_u, seen, k0 - 1, state.u),
            c0=_state_after(win_c, seen, k1 - 1, state.c0),
            v_next=_state_after(win_v, seen, 1, state.v_next),
            index=idx + jnp.int32(length))
        return out, (kv._replace(index=kv.index + jnp.int32(length)), state)
