"""Latent attention (multi-head latent attention, MLA; the DeepSeek-V2/V3
family): what a position keeps is ONE compressed latent shared by every
head, and the heads' keys and values are linear maps of it.

On a layer's normed input ``x [L, H]``, with ``n`` heads::

    c_q        = rmsnorm(x W_DQ)                       [L, q_rank]
    [q_n, q_r] = c_q W_UQ                              [L, n, dn], [L, n, dr]
    [c, k_r]   = x W_DKV                               [L, r], [L, dr]
    c          = rmsnorm(c);  k_r is ONE head, shared by all n
    q_r, k_r   = rotary(q_r), rotary(k_r)              interleaved pairs, YaRN
    [k_n, v]   = c W_UKV                               [L, n, dn], [L, n, dv]
    s[t,i,a]   = (q_n[t,a] . k_n[i,a] + q_r[t,a] . k_r[i]) * scale
    o[t,a]     = sum_{i<=t} softmax_i(s[t,i,a]) v[i,a];  out = concat_a(o) W_O

``scale = (dn + dr)^-0.5 * m^2``, ``m`` YaRN's ``mscale`` term (1 without
rope scaling).  That is the EXPANDED form.  With ``W_UKV = [W_UK | W_UV]``
a head the same function is, ABSORBED::

    q_l[t,a] = q_n[t,a] W_UK[a]^T                      [r]
    s[t,i,a] = (q_l[t,a] . c[i] + q_r[t,a] . k_r[i]) * scale
    o[t,a]   = (sum_i p_i c[i]) W_UV[a]

so a position keeps ``c`` (after its norm) and ``k_r`` (after its turn):
``r + dr`` values a layer, whatever the number of heads.  The cache holds
them side by side in ONE array a layer, ``[c | k_r | 0]`` padded to whole
128-lane tiles (``entry_width``: 640 for 512 + 64), so that a block is one
DMA, the score is one product against it and the values are its first ``r``
lanes.  The padding costs nothing the chip would not spend anyway: its
tiling pads a minor dimension of 64 (or 576) to the next 128 in HBM, and
the layout it prefers for a ``[.., 128, 64]`` array to avoid that (positions
minor-most) is one the kernel cannot read, so a separate rotary-key pool was
copied there and back every step (two pool-sized copies a layer).

**Two paths, chosen by where the chunk is known to start.**  A chunk with
no cache, or against a cache whose ``index`` is known to be 0 WHILE THE
PROGRAM IS TRACED (a cache made in the same trace, as
``DecodeSession._prefill`` makes the bucketed prompt's: ``gen_decode_cache``
hands a single sequence's ``index`` as a numpy zero, which no trace stages;
or a concrete zero in eager code), is a PROMPT from position 0 and runs
expanded over its own keys: ``n x (dn + dr + dv)`` values a position are
cheap to make once and the quadratic part runs at head sizes ``dn + dr`` /
``dv`` instead of ``r + dr`` / ``r`` (``ops.flash_attention
.causal_attention``).  Every other chunk (a decode step, a speculative
verify chunk of any length, whatever starts mid-way) runs absorbed against
the cache it has just been written to: the latents of the context are read
once, as keys and as values, and never expanded
(``ops.flash_attention.latent_decode_attention``: the fused kernel for a
chunk of at most ``ops.pallas_decode.MAX_KERNEL_QUERY_CHUNK`` positions, the
XLA composition for a longer one).  So the kernel's geometry chooses between
two programs of one function, never between two functions: no chunk is
attended without its cached context.  The composition's scores are
``[rows, n, chunk, context]`` in float32, which no prompt-sized chunk
affords: ``jit.cache.LatentLayout.prompt_from_zero`` makes the pool refuse
chunked prefill and prefix sharing, whose chunks start mid-way.
``W_UK`` and ``W_UV`` are views of ``kv_up``'s one weight, not copies.

The decode caches: :data:`LatentDecodeCache` (``latent [B, S, W]`` by
slot) and :data:`PagedLatentDecodeCache` (blocks ``[num_blocks, bs, W]``
behind a ``table``), laid out by ``jit.cache.LatentLayout`` /
``DenseLayout``.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Tensor
from .. import functional as F
from .common import Linear
from .layers import Layer
from .norm import RMSNorm

__all__ = ["LatentDecodeCache", "PagedLatentDecodeCache", "LatentAttention"]

LatentDecodeCache = collections.namedtuple(
    "LatentDecodeCache", ["latent", "index"])
PagedLatentDecodeCache = collections.namedtuple(
    "PagedLatentDecodeCache", ["latent", "table", "index"])
_LANES = 128


def _starts_at_zero(index) -> bool:
    """Whether a chunk against a cache at ``index`` is KNOWN, as the program
    is traced, to start at position 0: a traced index is not."""
    return not isinstance(index, jax.core.Tracer) \
        and not np.asarray(index).any()


class LatentAttention(Layer):
    """See the module docstring.  ``rope_scaling``: None, or YaRN's
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim`` (the published keys)."""

    def __init__(self, hidden_size: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None,
                 norm_epsilon: float = 1e-6):
        super().__init__()
        if qk_rope_head_dim % 2:
            raise InvalidArgumentError(
                "rotary positions turn pairs of channels: qk_rope_head_dim "
                "%d is odd" % qk_rope_head_dim)
        self.hidden_size, self.num_heads = int(hidden_size), int(num_heads)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), \
            int(kv_lora_rank)
        self.nope, self.rope, self.v_dim = int(qk_nope_head_dim), \
            int(qk_rope_head_dim), int(v_head_dim)
        n = self.num_heads
        self.q_down = Linear(hidden_size, q_lora_rank, bias_attr=False)
        self.q_norm = RMSNorm(q_lora_rank, norm_epsilon)
        self.q_up = Linear(q_lora_rank, n * (self.nope + self.rope),
                           bias_attr=False)
        self.kv_down = Linear(hidden_size, kv_lora_rank + self.rope,
                              bias_attr=False)
        self.kv_norm = RMSNorm(kv_lora_rank, norm_epsilon)
        self.kv_up = Linear(kv_lora_rank, n * (self.nope + self.v_dim),
                            bias_attr=False)
        self.o_proj = Linear(n * self.v_dim, hidden_size, bias_attr=False)
        #: values a position keeps in the cache: the latent, the rotary
        #: key, zeros up to whole 128-lane tiles
        self.entry_width = -(-(self.kv_lora_rank + self.rope) // _LANES) \
            * _LANES
        scaling = dict(rope_scaling or {})
        factor = float(scaling.get("factor", 1.0))
        self.inv_freq = F.yarn_inv_freq(
            self.rope, rope_theta, factor,
            int(scaling.get("original_max_position_embeddings", 1)),
            float(scaling.get("beta_fast", 32.0)),
            float(scaling.get("beta_slow", 1.0)))
        all_dim = float(scaling.get("mscale_all_dim", 0.0))
        #: what cos and sin are multiplied by (1 where both terms agree)
        self.rope_scale = F.yarn_mscale(factor, float(
            scaling.get("mscale", 1.0))) / F.yarn_mscale(factor, all_dim)
        m = F.yarn_mscale(factor, all_dim) if all_dim else 1.0
        self.sm_scale = float((self.nope + self.rope) ** -0.5 * m * m)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """A latent entry: by slot (``layout="dense"``) or in blocks behind
        a table (``"paged"``; ``num_blocks`` None sizes the pool to full
        capacity under the identity table, as ``MultiHeadAttention
        .gen_decode_cache`` does).  Float types only: an int8 latent has no
        head for a scale to ride with."""
        from ...jit.cache import LatentLayout

        if layout not in ("dense", "paged"):
            raise InvalidArgumentError(
                "cache layout must be 'dense' or 'paged', got %r"
                % (layout,))
        name = str(jnp.dtype(dtype))
        if name not in LatentLayout.payload_dtypes:
            raise InvalidArgumentError(
                "a latent cache entry is kept in one of %r, not %r: one "
                "latent serves every head, so there is no per-head scale "
                "for an int8 pool to carry"
                % (LatentLayout.payload_dtypes, name))
        # a single sequence's index is a numpy zero: made inside a trace
        # (the bucketed prefill) it stays a constant that ``forward`` can
        # read, where a ``jnp.zeros`` would be staged
        index = (jnp.zeros((batch_size,), jnp.int32) if per_slot
                 else np.zeros((), np.int32))
        if layout == "dense":
            return LatentDecodeCache(
                jnp.zeros((batch_size, max_length, self.entry_width), name),
                index)
        block_size = int(block_size)
        max_blocks = -(-int(max_length) // block_size)
        if num_blocks is None:
            num_blocks = 1 + batch_size * max_blocks
            table = 1 + jnp.arange(batch_size * max_blocks, dtype=jnp.int32) \
                .reshape(batch_size, max_blocks)
        else:
            num_blocks = int(num_blocks)
            if num_blocks < 2:
                raise InvalidArgumentError(
                    "paged cache needs num_blocks >= 2 (block 0 is the "
                    "reserved scratch block), got %d" % num_blocks)
            table = jnp.zeros((batch_size, max_blocks), jnp.int32)
        return PagedLatentDecodeCache(
            jnp.zeros((num_blocks, block_size, self.entry_width), name),
            table, index)

    def _kv_up_views(self):
        """``W_UK`` ``[r, n, dn]`` and ``W_UV`` ``[r, n, dv]``: slices of
        ``kv_up``'s one weight."""
        w = self.kv_up.weight.value.reshape(
            self.kv_lora_rank, self.num_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def _padded(self, *parts):
        """``parts`` side by side along the last axis, zeros up to
        ``entry_width``."""
        used = sum(p.shape[-1] for p in parts)
        zeros = jnp.zeros(parts[0].shape[:-1] + (self.entry_width - used,),
                          parts[0].dtype)
        return jnp.concatenate([*parts, zeros], axis=-1)

    def _write(self, cache, c, k_r, pos):
        """The chunk's entries ``[c | k_r | 0]`` into the cache at ``pos``
        (``[L]`` or ``[B, L]``)."""
        from ...ops.flash_attention import latent_cache_write

        b, length = c.shape[0], c.shape[1]
        entry = self._padded(c, k_r.astype(c.dtype))
        at = jnp.broadcast_to(pos, (b, length))
        if isinstance(cache, LatentDecodeCache):
            with jax.named_scope("cache_write"):
                return cache._replace(
                    latent=cache.latent.at[jnp.arange(b)[:, None], at].set(
                        entry.astype(cache.latent.dtype), mode="drop"))
        table = jnp.asarray(cache.table, jnp.int32)
        bs = cache.latent.shape[1]
        span = table.shape[1] * bs
        # a position past the table's span goes to the scratch block, as
        # the paged K/V write routes it
        logical = jnp.minimum(at // bs, table.shape[1] - 1)
        phys = jnp.where(at < span, table[jnp.arange(b)[:, None], logical],
                         0)
        return cache._replace(
            latent=latent_cache_write(cache.latent, entry, phys, at % bs))

    def forward(self, x, cache=None):
        from ...ops.flash_attention import (causal_attention,
                                            latent_decode_attention)

        b, length = x.shape[0], x.shape[1]
        n = self.num_heads
        with jax.named_scope("mla/q_down"):
            c_q = self.q_norm(self.q_down(x))
        with jax.named_scope("mla/q_up"):
            q = self.q_up(c_q).value.reshape(b, length, n,
                                             self.nope + self.rope)
            q = jnp.swapaxes(q, 1, 2)                         # [B, n, L, .]
            q_n, q_r = q[..., :self.nope], q[..., self.nope:]
        with jax.named_scope("mla/kv_down"):
            ck = self.kv_down(x)
            c = self.kv_norm(ck[..., :self.kv_lora_rank]).value
            k_r = ck.value[..., self.kv_lora_rank:]           # [B, L, dr]
        steps = jnp.arange(length, dtype=jnp.int32)
        if cache is None:
            pos = steps
        else:
            idx = jnp.asarray(cache.index, jnp.int32)
            pos = idx + steps if idx.ndim == 0 \
                else idx[:, None] + steps[None, :]
        with jax.named_scope("rope"):
            q_r = F.rotary_embedding_pairs(q_r, pos, self.inv_freq,
                                           self.rope_scale)
            k_r = F.rotary_embedding_pairs(k_r, pos, self.inv_freq,
                                           self.rope_scale)
        if cache is not None:
            cache = self._write(cache, c, k_r, pos)
        w_uk, w_uv = self._kv_up_views()
        if cache is not None and not _starts_at_zero(cache.index):
            # a chunk against the cache it was just written to: absorbed
            with jax.named_scope("mla/absorb"):
                q_l = jnp.einsum("bhld,rhd->bhlr", q_n, w_uk)
                q_cat = self._padded(q_l, q_r.astype(q_l.dtype))
            o_l = latent_decode_attention(
                q_cat, cache.latent, getattr(cache, "table", None), pos,
                self.kv_lora_rank, self.sm_scale)
            with jax.named_scope("mla/absorb"):
                o = jnp.einsum("bhlr,rhv->blhv", o_l, w_uv)
        else:
            # a prompt from position 0, over its own keys: expanded
            with jax.named_scope("mla/expand"):
                k_n = jnp.einsum("blr,rhd->bhld", c, w_uk)
                v = jnp.einsum("blr,rhv->bhlv", c, w_uv)
                k = jnp.concatenate(
                    [k_n, jnp.broadcast_to(k_r[:, None],
                                           (b, n, length, self.rope))],
                    axis=-1)
                qf = jnp.concatenate([q_n, q_r], axis=-1)
            with jax.named_scope("prefill_attn"):
                o = jnp.swapaxes(
                    causal_attention(qf, k, v, self.sm_scale), 1, 2)
        with jax.named_scope("mla/o_proj"):
            out = self.o_proj(Tensor(
                o.reshape(b, length, n * self.v_dim).astype(x.value.dtype),
                stop_gradient=True))
        if cache is None:
            return out
        return out, cache._replace(index=idx + jnp.int32(length))
