"""Gated power retention as a layer: the token mixer of the ``brumby``
family (an attention block retrained with the softmax product replaced by a
gated recurrence over a state of constant size; ``ops/power_retention.py``
has the mathematics and the state's layout).

It keeps ``nn.GroupedQueryAttention``'s projections, heads' RMSNorm and
rotary turn (``_qkv``) and adds one gate a K/V head: ``log_sigmoid`` of a
linear map, with bias, of the layer's normed input, taken in float32.

Its decode cache is :data:`RetentionDecodeCache`, one per layer: ``state``
``[B, Hkv, dv, D]`` and ``norm`` ``[B, Hkv, 1, D]`` in float32, ``index``
(positions consumed: a scalar, or ``[B]`` in a pool) and ``limit`` (a
position at or past it is an identity step: a padded bucket's tail in the
prefill, a free slot's row in a pool's step).  ``jit.cache.RecurrentLayout``
places, splices, freezes and spills it with ``nn.ssm``'s cache.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Tensor
from ...ops import power_retention as ops
from .common import Linear
from .transformer import GroupedQueryAttention

__all__ = ["RetentionDecodeCache", "PowerRetention"]

class RetentionDecodeCache(collections.namedtuple(
        "RetentionDecodeCache", ["state", "norm", "index", "limit"])):
    __slots__ = ()
    #: a prefill starts from an empty state, and ``RecurrentLayout
    #: .begin_prefill`` says so where a trace can see it: it hands the
    #: layers ``state=None, norm=None``.  A layer told so reads no state
    #: (``ops.power_retention_prefill``); one handed arrays continues
    #: from them, whatever they hold
    empty_as_none = True


class PowerRetention(GroupedQueryAttention):
    """``num_heads`` query heads on ``num_kv_heads`` K/V heads of
    ``head_dim``, degree 2.  ``forward(x)`` runs a whole sequence from an
    empty state; ``forward(x, cache=...)`` continues from the cache: one
    position is the decode step, more the chunked prefill."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, rope_theta: float = 10000.0,
                 qk_norm: bool = True, norm_epsilon: float = 1e-6):
        super().__init__(embed_dim, num_heads, num_kv_heads, head_dim,
                         rope_theta=rope_theta, qk_norm=qk_norm,
                         norm_epsilon=norm_epsilon)
        ops.phi_size(head_dim)
        self.gate_proj = Linear(embed_dim, num_kv_heads)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "recurrent", block_size: int = 32,
                         num_blocks=None):
        if layout != "recurrent":
            raise InvalidArgumentError(
                "PowerRetention keeps a state of constant size, not "
                "positional K/V: cache_layout=%r does not exist for it; "
                "pass cache_layout='recurrent'" % (layout,))
        if str(dtype) != "float32":
            raise InvalidArgumentError(
                "the retention state supports only dtype='float32' (got "
                "%r): every later token reads what each step leaves in it"
                % (dtype,))
        d_phi = ops.phi_size(self.head_dim)
        shape = (batch_size, self.num_kv_heads)
        return RetentionDecodeCache(
            state=jnp.zeros(shape + (self.head_dim, d_phi), jnp.float32),
            norm=jnp.zeros(shape + (1, d_phi), jnp.float32),
            index=(jnp.zeros((batch_size,), jnp.int32) if per_slot
                   else jnp.asarray(0, jnp.int32)),
            limit=jnp.asarray(int(max_length), jnp.int32))

    def forward(self, x, attn_mask=None, cache=None):
        if attn_mask is not None:
            raise InvalidArgumentError(
                "PowerRetention is causal by construction; pass "
                "attn_mask=None")
        q, k, v, pos = self._qkv(x, cache)
        with jax.named_scope("retention/gate"):
            # float32 throughout: a bias of 8 has steps of 1/16 in
            # bfloat16, wider than what a token adds to it, and the sum
            # of these logs over a context is the decay every weight sees
            f32 = lambda t: t.value.astype(jnp.float32)
            lg = jax.nn.log_sigmoid(
                jnp.matmul(f32(x), f32(self.gate_proj.weight))
                + f32(self.gate_proj.bias))
            lg = jnp.swapaxes(lg, 1, 2)                     # [B, Hkv, L]
        q, k, v = q.value, k.value, v.value
        scale = self.head_dim ** -0.5
        b, length = x.shape[0], x.shape[1]
        if cache is None:
            y, _, _ = ops.power_retention_prefill(q, k, v, lg, scale=scale)
            return self.out_proj(self._merge_heads(
                Tensor(y, stop_gradient=True)))
        keep = jnp.broadcast_to(pos, (b, length)) \
            < jnp.reshape(cache.limit, (-1, 1))
        if cache.state is None:         # a prefill from an empty state
            y, state, norm = ops.power_retention_prefill(
                q, k, v, lg, keep, scale=scale)
        elif length == 1:
            y, state, norm = ops.power_retention_step(
                q[:, :, 0], k[:, :, 0], v[:, :, 0], lg[:, :, 0],
                cache.state, cache.norm, keep[:, 0], scale=scale)
            y = y[:, :, None]
        else:
            y, state, norm = ops.power_retention_chunked(
                q, k, v, lg, cache.state, cache.norm, keep, scale=scale)
        out = self.out_proj(self._merge_heads(Tensor(y, stop_gradient=True)))
        return out, cache._replace(
            state=state, norm=norm,
            index=jnp.asarray(cache.index, jnp.int32) + jnp.int32(length))
