"""Transformer layers (reference: python/paddle/nn/layer/transformer.py:
MultiHeadAttention:109, TransformerEncoderLayer:398, TransformerEncoder:622,
TransformerDecoderLayer:721, TransformerDecoder:940, Transformer:1112).

TPU-native: attention is a single fused einsum chain
(``F.scaled_dot_product_attention``), batched [B, H, L, D] for the MXU; masks
are additive bf16-safe; cache objects are plain tuples for lax.scan-friendly
incremental decoding.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import numpy as np

from ...core.errors import InvalidArgumentError
from .. import functional as F
from .. import initializer as I
from .common import Dropout, Linear
from .layers import Layer
from .norm import LayerNorm, RMSNorm


def _convert_attn_mask(mask, dtype):
    """bool mask (True=keep) -> additive; numeric passes through."""
    from ... import tensor as T

    if mask is None:
        return None
    if mask.dtype == np.bool_ or str(mask.dtype) == "bool":
        return T.scale(T.cast(T.logical_not(mask), dtype), -1e9)
    return T.cast(mask, dtype)


# Decode-cache storage dtypes: the float dtypes store K/V verbatim;
# "int8" stores K/V quantized with per-head fp32 absmax scales
# (ops.quantize_kv) riding alongside the buffers, dequantized inside the
# attention composition — halving (vs bf16) or quartering (vs fp32) the
# HBM bytes every decode step streams.
SUPPORTED_CACHE_DTYPES = ("float32", "bfloat16", "float16", "int8")


def normalize_cache_dtype(dtype) -> str:
    """Canonical dtype name for a decode cache, or a typed error naming
    the supported set — checked at cache allocation AND at
    ``DecodeSession`` construction, because an unsupported dtype would
    otherwise surface as a shape/astype failure deep inside the first
    compiled step."""
    import jax.numpy as jnp

    try:
        name = jnp.dtype(dtype).name
    except TypeError:
        name = str(dtype)
    if name not in SUPPORTED_CACHE_DTYPES:
        raise InvalidArgumentError(
            "unsupported KV cache dtype %r; supported cache dtypes: %s "
            "('int8' stores quantized K/V with per-head fp32 scales)"
            % (dtype, list(SUPPORTED_CACHE_DTYPES)))
    return name


class MultiHeadAttention(Layer):
    """paddle.nn.MultiHeadAttention parity (transformer.py:109)."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])
    # Decode-engine cache (jit/decode.py): PREALLOCATED [B, H, max_len, D]
    # K/V buffers + a cache index (scalar int32, or [B] int32 for
    # slot-batched serving).  Unlike ``Cache`` (which concatenates and so
    # changes shape — retracing every step), writes go through
    # lax.dynamic_update_slice and the index advances, so every decode
    # step has IDENTICAL shapes: one XLA compilation, donate-able
    # buffers, O(1) per-token attention against the valid prefix.
    # ``k_scale``/``v_scale`` are None for float caches; for the int8
    # cache they are fp32 per-head absmax scales (one per written
    # position per head — dense [B, H, max_len]), quantized-on-write by
    # the same dynamic_update_slice path that writes K/V.  None leaves
    # vanish from the jit pytree, so the float cache's compiled steps
    # are byte-identical to the pre-quantization ones.
    DecodeCache = collections.namedtuple(
        "DecodeCache", ["k", "v", "index", "k_scale", "v_scale"],
        defaults=(None, None))
    # Paged decode cache (vLLM block-table scheme): K/V live in a GLOBAL
    # pool of fixed-size blocks [num_blocks, H, block_size, D] and each
    # row owns a [max_blocks] int32 row of ``table`` mapping its logical
    # block j to a physical pool row.  Physical block 0 is a reserved
    # scratch block unmapped logical blocks point at.  All shapes stay
    # static — only table VALUES vary — so the "exactly two compiles"
    # contract of the dense cache is preserved while cache HBM scales
    # with ALLOCATED tokens, not max_len × rows.
    # Paged scales live in per-block pools ([num_blocks, H, block_size])
    # gathered through the same table as K/V, so a block carries its own
    # scales wherever the allocator maps it.
    PagedDecodeCache = collections.namedtuple(
        "PagedDecodeCache", ["k", "v", "table", "index",
                             "k_scale", "v_scale"],
        defaults=(None, None))

    # Window decode cache (``GroupedQueryAttention(window=)`` under the
    # paged layout): K/V blocks as ``PagedDecodeCache`` holds them, behind
    # a ``table`` ``[B, ring]`` that is a RING: position ``p`` lives at
    # entry ``(p // block_size) % ring``, so a block behind the window is
    # overwritten by a later one and a row pins ``ring`` blocks whatever
    # its context.  ``window`` (an int32 scalar) is the band's width; the
    # field is what tells the entry's kind (``jit.cache.entry_layout``).
    # Float pools only.
    WindowDecodeCache = collections.namedtuple(
        "WindowDecodeCache", ["k", "v", "table", "index", "window"])

    def __init__(
        self,
        embed_dim: int,
        num_heads: int,
        dropout: float = 0.0,
        kdim: Optional[int] = None,
        vdim: Optional[int] = None,
        need_weights: bool = False,
        weight_attr=None,
        bias_attr=None,
    ):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.head_dim = embed_dim // num_heads
        if self.head_dim * num_heads != embed_dim:
            raise InvalidArgumentError("embed_dim %d not divisible by num_heads %d" % (embed_dim, num_heads))
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self._sep_attn = None  # set by enable_sequence_parallel

    @property
    def kv_heads(self) -> int:
        """Heads the decode cache holds: one K/V head a query head here;
        ``GroupedQueryAttention`` holds fewer."""
        return self.num_heads

    def _last_visible(self, pos):
        """The last key position a query at ``pos`` may attend, the form
        the cached forwards hand the attention ops their mask in: its own
        position for a causal decoder."""
        return pos

    def enable_sequence_parallel(self, group=None, mode: str = "ring",
                                 causal: bool = False):
        """Sequence-parallel attention over the ``sep`` mesh axis (SURVEY §5.7).

        Activations stay global-view; the attention inner product runs inside
        ``shard_map`` with the sequence dim sharded on the sep axis:
        ``mode='ring'`` rotates K/V blocks with ``lax.ppermute`` (ICI
        neighbor exchange + online softmax), ``mode='ulysses'`` reshards
        seq→heads with ``lax.all_to_all``.  GSPMD propagates the sequence
        sharding through the surrounding per-position layers, so the rest of
        the block parallelizes for free.

        Constraints (flash-style kernels): no attention-prob dropout, no
        arbitrary additive masks — causality is expressed via ``causal``.
        """
        from jax.sharding import PartitionSpec as P

        from ...core.errors import InvalidArgumentError
        from ...distributed.collective import shard_map
        from ...distributed.meta_parallel.sequence_parallel import (
            ring_attention, ulysses_attention)
        from ...framework.dispatch import make_op

        if self.dropout:
            raise InvalidArgumentError(
                "sequence-parallel attention has no prob-dropout path; "
                "construct the layer with dropout=0.0")
        if mode not in ("ring", "ulysses"):
            raise InvalidArgumentError(
                "sequence_parallel mode must be 'ring' or 'ulysses', got %r"
                % mode)
        if group is None:
            from ...distributed.fleet import fleet

            group = fleet.get_hybrid_communicate_group() \
                .get_sep_parallel_group()
        ax = group.axis_name
        if mode == "ulysses" and self.num_heads % group.nranks != 0:
            raise InvalidArgumentError(
                "ulysses needs num_heads %% sep_degree == 0, got H=%d n=%d"
                % (self.num_heads, group.nranks))
        inner = ring_attention if mode == "ring" else ulysses_attention

        spec = P(None, None, ax, None)
        sep_attn = shard_map(
            lambda qq, kk, vv: inner(qq, kk, vv, ax, causal=causal),
            mesh=group.mesh, in_specs=(spec, spec, spec), out_specs=spec)
        self._sep_attn = make_op(sep_attn, op_name="sep_attention_" + mode)
        self._sep_causal = causal
        return self

    def _split_heads(self, x):
        from ... import tensor as T

        b, l = x.shape[0], x.shape[1]
        x = T.reshape(x, [b, l, self.num_heads, self.head_dim])
        return T.transpose(x, [0, 2, 1, 3])  # [B, H, L, D]

    def _merge_heads(self, x):
        from ... import tensor as T

        b, h, l, d = x.shape
        return T.reshape(T.transpose(x, [0, 2, 1, 3]), [b, l, h * d])

    def gen_cache(self, key, value=None, type=None):
        from ... import tensor as T

        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None else key))
            return self.StaticCache(k, v)
        if value is None:
            # incremental cache seeded empty: shapes [B, H, 0, D]
            b = key.shape[0]
            k = T.zeros([b, self.num_heads, 0, self.head_dim])
            v = T.zeros([b, self.num_heads, 0, self.head_dim])
            return self.Cache(k, v)
        return self.Cache(key, value)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None,
                         planes: int = 1):
        """Preallocated decode cache; leaves are RAW jax arrays (not
        Tensors) so the cache threads through jitted prefill/decode as a
        donated pytree.  The index is 0 (scalar, or [B] when
        ``per_slot`` — the GenerationPool's slot-batched layout where
        each row decodes at its own position).

        ``dtype="int8"`` stores K/V quantized (per-head fp32 absmax
        scales in ``k_scale``/``v_scale`` — dense [B, H, max_len], paged
        [num_blocks, H, block_size]); unsupported dtypes raise a typed
        error naming :data:`SUPPORTED_CACHE_DTYPES`.

        ``layout="dense"``: zeroed [B, H, max_len, D] K/V buffers.

        ``layout="paged"``: a global block pool
        [num_blocks, H, block_size, D] plus a [B, max_blocks] int32 block
        table (``PagedDecodeCache``).  Physical block 0 is reserved as a
        scratch block.  With ``num_blocks=None`` the pool is sized to
        full capacity (1 + B * max_blocks) and the table is the IDENTITY
        mapping — self-managed, no allocator needed (DecodeSession's
        aligned batches).  An EXPLICIT ``num_blocks`` means an external
        allocator (inference.GenerationPool) owns the mapping: the table
        starts all-zeros (everything unmapped → scratch) and the
        allocator writes rows as it maps blocks.

        ``planes`` > 1: the entry holds that many K/V PLANES, one for
        each time the layer runs in a forward (``models.LoopedLM`` runs
        its stack several times on shared weights), side by side on the
        HEAD axis: dense ``[B, planes * H, max_len, D]``, paged
        ``[num_blocks, planes * H, block_size, D]``.  One ``index`` and
        one ``table``: a position's planes lie in the same block, so
        whatever takes, splices, masks, spills or frees a block moves
        them together and the allocator counts what it counted; a block's
        BYTES are ``planes`` times a plane's.  The cached forward is told
        which plane it attends and writes (``plane=``, a traced index).
        An int8 plane is refused: its scales would ride the head axis
        too, and neither the kernel nor the composition takes a plane of
        them."""
        import jax.numpy as jnp

        if layout not in ("dense", "paged"):
            raise InvalidArgumentError(
                "cache layout must be 'dense' or 'paged', got %r"
                % (layout,))
        dtype = normalize_cache_dtype(dtype)
        quant = dtype == "int8"
        planes = int(planes)
        if planes < 1:
            raise InvalidArgumentError(
                "a cache entry holds planes >= 1 K/V planes, got %d"
                % planes)
        if planes > 1 and quant:
            raise InvalidArgumentError(
                "cache_dtype='int8' does not exist for an entry of %d K/V "
                "planes (one a pass of a stack run several times): the "
                "scales of a plane would be addressed by the pass index "
                "like its K/V, which no attention route does; keep the "
                "planes in float32 or bfloat16" % planes)
        heads = planes * self.kv_heads
        index = (jnp.zeros((batch_size,), jnp.int32) if per_slot
                 else jnp.zeros((), jnp.int32))
        if layout == "dense":
            shape = (batch_size, heads, max_length, self.head_dim)
            scales = ((jnp.zeros(shape[:-1], jnp.float32),) * 2 if quant
                      else (None, None))
            return self.DecodeCache(jnp.zeros(shape, dtype),
                                    jnp.zeros(shape, dtype), index,
                                    *scales)
        block_size = int(block_size)
        if block_size < 1:
            raise InvalidArgumentError(
                "paged cache needs block_size >= 1, got %d" % block_size)
        max_blocks = -(-int(max_length) // block_size)
        if num_blocks is None:
            num_blocks = 1 + batch_size * max_blocks
            table = 1 + jnp.arange(batch_size * max_blocks,
                                   dtype=jnp.int32).reshape(batch_size,
                                                            max_blocks)
        else:
            num_blocks = int(num_blocks)
            if num_blocks < 2:
                raise InvalidArgumentError(
                    "paged cache needs num_blocks >= 2 (block 0 is the "
                    "reserved scratch block), got %d" % num_blocks)
            table = jnp.zeros((batch_size, max_blocks), jnp.int32)
        shape = (num_blocks, heads, block_size, self.head_dim)
        scales = ((jnp.zeros(shape[:-1], jnp.float32),) * 2 if quant
                  else (None, None))
        return self.PagedDecodeCache(jnp.zeros(shape, dtype),
                                     jnp.zeros(shape, dtype), table, index,
                                     *scales)

    def _decode_forward(self, q, k_new, v_new, attn_mask, cache,
                        plane=None):
        """Shape-static cached attention: write the new K/V chunk into the
        preallocated buffers at ``cache.index``, attend the queries over
        the valid prefix (causal across prefix + chunk), advance the
        index.  Returns (raw attention out [B, H, L, D], new cache).

        ``plane`` (an int32 scalar, traced): the buffers hold several
        planes of ``kv_heads`` heads (``gen_decode_cache(planes=)``) and
        this call writes and attends plane ``plane`` alone."""
        import jax
        import jax.numpy as jnp

        from ...framework.tensor import Tensor as _T
        from ...ops.flash_attention import decode_attention, quantize_kv

        def raw(x):
            return x.value if isinstance(x, _T) else jnp.asarray(x)

        q_, k_new, v_new = raw(q), raw(k_new), raw(v_new)
        k_buf, v_buf = raw(cache.k), raw(cache.v)
        ks_buf, vs_buf = cache.k_scale, cache.v_scale
        quant = ks_buf is not None
        if quant:
            # quantize-on-write: the chunk's per-head absmax scales are
            # computed in-trace and written through the SAME slice /
            # scatter addressing as the int8 values
            k_new, k_s = quantize_kv(k_new)
            v_new, v_s = quantize_kv(v_new)
        idx = jnp.asarray(cache.index, jnp.int32)
        b, _, length, _ = q_.shape
        # the first head written: 0, or the plane's
        base = 0 if plane is None \
            else jnp.asarray(plane, jnp.int32) * self.kv_heads
        if idx.ndim == 0:
            # aligned batch (DecodeSession): one slice write for the chunk
            k_buf = jax.lax.dynamic_update_slice(
                k_buf, k_new.astype(k_buf.dtype), (0, base, idx, 0))
            v_buf = jax.lax.dynamic_update_slice(
                v_buf, v_new.astype(v_buf.dtype), (0, base, idx, 0))
            if quant:
                ks_buf = jax.lax.dynamic_update_slice(ks_buf, k_s,
                                                      (0, 0, idx))
                vs_buf = jax.lax.dynamic_update_slice(vs_buf, v_s,
                                                      (0, 0, idx))
            q_pos = self._last_visible(idx + jnp.arange(length))  # [L]
        else:
            # slot-batched decode/verify: each row writes its L-token
            # chunk at its OWN position — a scatter over [B, L]
            # (row, pos) pairs.  L is 1 for the steady-state pool step
            # and spec_k+1 for the speculative verify chunk; positions
            # past max_len (a speculative tail overshooting the cache)
            # are DROPPED by the scatter, never clamped onto valid rows.
            rows = jnp.arange(b)[:, None]                       # [B,1]
            pos = idx[:, None] + jnp.arange(length)[None, :]    # [B,L]
            if plane is None:
                at = (rows, slice(None), pos)
            else:
                # the plane's heads, indexed beside (row, pos): [B,L,H]
                at = (rows[..., None], base + jnp.arange(self.kv_heads),
                      pos[..., None])
            k_buf = k_buf.at[at].set(
                k_new.transpose(0, 2, 1, 3).astype(k_buf.dtype),
                mode="drop")
            v_buf = v_buf.at[at].set(
                v_new.transpose(0, 2, 1, 3).astype(v_buf.dtype),
                mode="drop")
            if quant:
                ks_buf = ks_buf.at[rows, :, pos].set(
                    k_s.transpose(0, 2, 1), mode="drop")
                vs_buf = vs_buf.at[rows, :, pos].set(
                    v_s.transpose(0, 2, 1), mode="drop")
            q_pos = self._last_visible(pos)                     # [B,L]
        if attn_mask is not None:
            # a caller's mask is keyed to the CHUNK length while the
            # score axis here is the cache length max_len — combining
            # them would mis-broadcast; the cached path derives its own
            # causal-prefix mask from the index
            raise InvalidArgumentError(
                "decode-cache attention derives its mask from the cache "
                "index (causal over the valid prefix); additive "
                "attn_mask is not supported with a DecodeCache — pass "
                "attn_mask=None, or use the uncached forward")
        # masking travels in index form (q_pos = each query's last
        # visible key): the composition route rebuilds the exact
        # additive causal-prefix mask this code used to build inline,
        # while the fused pallas route masks in-register (§5l)
        if plane is None:
            k_read, v_read = k_buf, v_buf
        else:
            # a dense cache is read whole by the composition: the plane
            # is its slice (a slot's slab, not a pool under a table; the
            # paged entry's plane is never sliced out)
            k_read, v_read = (jax.lax.dynamic_slice_in_dim(
                x, base, self.kv_heads, axis=1) for x in (k_buf, v_buf))
        # (a window layer's dense cache keeps every position: the band is
        # the mask's lower edge)
        out = decode_attention(q_, k_read, v_read, q_pos=q_pos,
                               k_scale=ks_buf, v_scale=vs_buf,
                               window=getattr(self, "window", None))
        return out, self.DecodeCache(k_buf, v_buf, idx + length,
                                     ks_buf, vs_buf)

    def _paged_decode_forward(self, q, k_new, v_new, attn_mask, cache,
                              plane=None):
        """Block-table cached attention: the new K/V chunk is scattered
        into the global block pool THROUGH the row's block table, queries
        attend over the gathered valid prefix, the index advances.  Same
        masking/ordering discipline as ``_decode_forward`` — the layouts
        are token-identical under greedy decoding — but writes address
        ``pool[table[row, pos // bs], :, pos % bs, :]`` so the bytes a
        step touches are the row's MAPPED blocks, not a dense
        [B, H, max_len, D] slab.

        ``plane`` (an int32 scalar, traced): the pools hold several planes
        of ``kv_heads`` heads an entry (``gen_decode_cache(planes=)``);
        the write and the attention address plane ``plane`` through the
        one table, by a head offset (``ops.flash_attention``)."""
        import jax.numpy as jnp

        from ...framework.tensor import Tensor as _T
        from ...ops.flash_attention import (paged_cache_write,
                                            paged_decode_attention,
                                            paged_kv_write, quantize_kv)

        def raw(x):
            return x.value if isinstance(x, _T) else jnp.asarray(x)

        if attn_mask is not None:
            raise InvalidArgumentError(
                "decode-cache attention derives its mask from the cache "
                "index (causal over the valid prefix); additive "
                "attn_mask is not supported with a DecodeCache — pass "
                "attn_mask=None, or use the uncached forward")
        q_, k_new, v_new = raw(q), raw(k_new), raw(v_new)
        k_pool, v_pool = raw(cache.k), raw(cache.v)
        ks_pool = getattr(cache, "k_scale", None)
        vs_pool = getattr(cache, "v_scale", None)
        quant = ks_pool is not None
        if quant:
            # quantize-on-write; scales scatter into the per-block scale
            # pools through the SAME (phys, off) addressing as K/V, so a
            # block and its scales can never diverge
            k_new, k_s = quantize_kv(k_new)
            v_new, v_s = quantize_kv(v_new)
        table = jnp.asarray(cache.table, jnp.int32)
        idx = jnp.asarray(cache.index, jnp.int32)
        b, _, length, _ = q_.shape
        bs = k_pool.shape[2]
        s = table.shape[1] * bs
        if isinstance(cache, self.WindowDecodeCache):
            return self._window_decode_forward(q_, k_new, v_new, cache)
        if idx.ndim == 0:
            # aligned batch (DecodeSession): every row writes the same
            # chunk positions through its own table row
            q_pos = idx + jnp.arange(length)                    # [L]
            phys = table[:, q_pos // bs]                        # [B, L]
            off = jnp.broadcast_to((q_pos % bs)[None, :], (b, length))
        else:
            # slot-batched decode/verify: each row writes its L-token
            # chunk at its OWN position, addressed through ITS table row
            # (L=1 steady-state pool step, L=spec_k+1 speculative
            # verify).  Positions past the table span are routed to the
            # scratch block — the same masking discipline as slot churn
            # — so a speculative tail can never clamp onto a real block.
            rows = jnp.arange(b)[:, None]                       # [B,1]
            q_pos = idx[:, None] + jnp.arange(length)[None, :]  # [B,L]
            logical = jnp.minimum(q_pos // bs, table.shape[1] - 1)
            phys = jnp.where(q_pos < s, table[rows, logical], 0)
            off = q_pos % bs
        # the plane's first head, for the write and for the attention
        at = {} if plane is None else {
            "head_base": jnp.asarray(plane, jnp.int32) * self.kv_heads}
        # K and V together: one kernel for both pools where the chunk is
        # decode-sized and the pools float, else a scatter each
        k_pool, v_pool = paged_kv_write(k_pool, v_pool, k_new, v_new, phys,
                                        off, **at)
        if quant:
            ks_pool = paged_cache_write(ks_pool, k_s, phys, off)
            vs_pool = paged_cache_write(vs_pool, v_s, phys, off)
        # masking travels in index form (see _decode_forward): the
        # composition rebuilds the inline additive mask op-for-op; the
        # fused route walks the table in-kernel and masks in-register
        out = paged_decode_attention(q_, k_pool, v_pool, table,
                                     q_pos=self._last_visible(q_pos),
                                     k_scale=ks_pool, v_scale=vs_pool,
                                     **(dict(at, plane_heads=self.kv_heads)
                                        if at else {}))
        return out, cache._replace(
            k=k_pool, v=v_pool, k_scale=ks_pool, v_scale=vs_pool,
            index=idx + length)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        from ... import tensor as T

        key = query if key is None else key
        value = key if value is None else value
        q = self._split_heads(self.q_proj(query))
        if isinstance(cache, (self.DecodeCache, self.PagedDecodeCache)):
            from ...framework.tensor import Tensor as _T

            k_new = self._split_heads(self.k_proj(key))
            v_new = self._split_heads(self.v_proj(value))
            fwd = (self._decode_forward
                   if isinstance(cache, self.DecodeCache)
                   else self._paged_decode_forward)
            out_raw, cache = fwd(q, k_new, v_new, attn_mask, cache)
            merged = self._merge_heads(_T(out_raw, stop_gradient=True))
            # row-parallel seam 1 (docs §5r): inside a decode trace with
            # the quantized-collective seam installed, the out_proj
            # reduction goes through the explicit int8 qpsum instead of
            # the GSPMD fp32 all-reduce; None = dense path, as traced
            # before the seam existed
            out = _row_parallel_seam(self.out_proj, merged)
            if out is None:
                out = self.out_proj(merged)
            if self.need_weights:
                return out, None, cache
            return out, cache
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value))
            if isinstance(cache, self.Cache):
                k = T.concat([cache.k, k], axis=2)
                v = T.concat([cache.v, v], axis=2)
                cache = self.Cache(k, v)

        if self._sep_attn is not None:
            if attn_mask is not None:
                raise InvalidArgumentError(
                    "sequence-parallel attention supports causality via "
                    "enable_sequence_parallel(causal=True), not additive "
                    "masks; pass attn_mask=None")
            if cache is not None:
                raise InvalidArgumentError(
                    "sequence-parallel attention does not support decode "
                    "caches; disable SP for incremental decoding")
            out = self._sep_attn(q, k, v)
        else:
            mask = _convert_attn_mask(attn_mask, q.dtype)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=self.dropout, training=self.training
            )
        out = self.out_proj(self._merge_heads(out))
        if isinstance(cache, self.Cache):
            return (out, cache) if not self.need_weights else (out, None, cache)
        if self.need_weights:
            return out, None
        return out


class GroupedQueryAttention(MultiHeadAttention):
    """Self-attention of the Llama/Qwen3 family: ``num_heads`` query heads
    on ``num_kv_heads`` K/V heads of ``head_dim`` (query head ``n`` reads
    K/V head ``n // (num_heads / num_kv_heads)``), RMSNorm over each
    head's channels of q and k (``qk_norm``), rotary positions
    (``rope_theta=None``: no position term at all, for a model whose other
    layers carry position), no bias.

    The decode caches (``gen_decode_cache``, dense and paged) hold the
    K/V heads, so their bytes and the decode step's reads fall by the
    group size; positions come from the cache index, per slot.

    ``block_length`` set: generation by diffusion over blocks.  Positions
    ``[k * block_length, (k + 1) * block_length)`` are one block whose
    rows see each other and every earlier block: the mask is block-causal,
    with and without a cache.

    ``window`` set: WINDOW attention.  Position ``i`` sees the ``window``
    positions that end at its own, ``i - window < j <= i`` (the band's
    edge is the mask ``i - j < window``: its own key is one of the
    ``window``), with and without a cache.  Under the paged layout the
    layer's cache entry is then a ``WindowDecodeCache``: a RING of
    ``window / block_size + 1`` blocks a row (``gen_decode_cache``), the
    decode step's kernel walking from the band's first entry to its last
    (``ops.pallas_decode``); a dense cache keeps every position under the
    banded mask.

    A PROMPT (a chunk of more than one position against a cache whose
    index is known to be 0 while the program is traced, as
    ``nn.LatentAttention`` tells one) of a window layer, and of any causal
    layer where the flash kernel runs, attends its OWN keys
    (``ops.flash_attention.prompt_attention``): no ``L x L`` scores in HBM
    from ``CAUSAL_FLASH_MIN_SEQ`` positions up.  A longer chunk that
    starts mid-way against a window entry is refused: the ring may have
    lost part of its band."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, rope_theta: Optional[float] = 10000.0,
                 qk_norm: bool = True, norm_epsilon: float = 1e-6,
                 block_length: Optional[int] = None,
                 window: Optional[int] = None):
        Layer.__init__(self)
        if window is not None and (int(window) < 1
                                   or block_length is not None):
            raise InvalidArgumentError(
                "window=%r: a band of at least one position, and not "
                "beside block_length (a block-causal mask has no band)"
                % (window,))
        self.window = None if window is None else int(window)
        if num_kv_heads < 1 or num_heads % num_kv_heads:
            raise InvalidArgumentError(
                "num_heads %d is not a whole multiple of num_kv_heads %d"
                % (num_heads, num_kv_heads))
        if rope_theta is not None and head_dim % 2:
            raise InvalidArgumentError(
                "rotary positions turn pairs of channels: head_dim %d is "
                "odd" % head_dim)
        self.embed_dim = self.kdim = self.vdim = embed_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.dropout, self.need_weights = 0.0, False
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.block_length = None if block_length is None \
            else int(block_length)
        self.q_proj = Linear(embed_dim, num_heads * head_dim,
                             bias_attr=False)
        self.k_proj = Linear(embed_dim, num_kv_heads * head_dim,
                             bias_attr=False)
        self.v_proj = Linear(embed_dim, num_kv_heads * head_dim,
                             bias_attr=False)
        self.out_proj = Linear(num_heads * head_dim, embed_dim,
                               bias_attr=False)
        self.q_norm = RMSNorm(head_dim, norm_epsilon) if qk_norm else None
        self.k_norm = RMSNorm(head_dim, norm_epsilon) if qk_norm else None
        self._sep_attn = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def enable_sequence_parallel(self, group=None, mode: str = "ring",
                                 causal: bool = False):
        raise InvalidArgumentError(
            "GroupedQueryAttention has no sequence-parallel form")

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None,
                         planes: int = 1):
        """``MultiHeadAttention.gen_decode_cache``; a WINDOW layer under
        the paged layout hands out a ``WindowDecodeCache`` instead: pools
        ``[1 + batch_size * ring, Hkv, block_size, D]`` (block 0 the
        scratch), a table ``[batch_size, ring]`` whose row ``s`` is blocks
        ``1 + s * ring ...``: the ring's blocks are the ROW'S OWN, fixed
        here, and no allocator maps them.  With an explicit ``num_blocks``
        (a pool's allocator owns the OTHER layers' tables; the number
        itself is not this entry's) ``ring = window / block_size + 1``,
        held to the blocks ``max_length`` spans; without (a session's
        self-managed cache, a prefill's row) the ring spans ``max_length``
        whole and never wraps, so a prompt of any length is written in
        order and ``jit.cache.WindowLayout.insert_entry`` takes its last
        ring of blocks.  The index of a single sequence is a numpy zero,
        which no trace stages: a chunk against it is known to be a
        prompt."""
        if self.window is None or layout != "paged":
            return super().gen_decode_cache(
                batch_size, max_length, dtype, per_slot, layout,
                block_size, num_blocks, planes)
        import jax.numpy as jnp

        dtype = normalize_cache_dtype(dtype)
        block_size = int(block_size)
        if dtype == "int8" or planes != 1:
            raise InvalidArgumentError(
                "a window entry (window=%d) is a float pool of one K/V "
                "plane: cache_dtype='int8' has no windowed attention route "
                "(its scales would ride the ring too)" % self.window)
        if block_size < 1 or self.window % block_size:
            raise InvalidArgumentError(
                "window=%d is not whole blocks of block_size=%d: the ring "
                "of a window entry is window / block_size + 1 blocks, and "
                "the band's first block is found by a division"
                % (self.window, block_size))
        span = -(-int(max_length) // block_size)
        ring = span if num_blocks is None \
            else min(self.window // block_size + 1, span)
        shape = (1 + batch_size * ring, self.kv_heads, block_size,
                 self.head_dim)
        table = 1 + jnp.arange(batch_size * ring, dtype=jnp.int32) \
            .reshape(batch_size, ring)
        index = jnp.zeros((batch_size,), jnp.int32) if per_slot \
            else np.zeros((), np.int32)
        return self.WindowDecodeCache(
            jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), table, index,
            np.asarray(self.window, np.int32))

    def _window_decode_forward(self, q, k_new, v_new, cache):
        """``_paged_decode_forward`` for a window entry: the chunk's K/V
        rows go to ``pool[table[row, (pos // bs) % ring], :, pos % bs]``
        and the queries attend the band that ends at each.  One position a
        row (the decode step); a longer chunk only as a prompt from
        position 0 that the ring holds whole, which ``forward`` has
        attended over its own keys: it hands ``q`` None and only the write
        is made."""
        import jax.numpy as jnp

        from ...ops.flash_attention import (paged_decode_attention,
                                            paged_kv_write)

        table = jnp.asarray(cache.table, jnp.int32)
        idx = jnp.asarray(cache.index, jnp.int32)
        b, _, length, _ = k_new.shape
        bs, ring = cache.k.shape[2], table.shape[1]
        steps = jnp.arange(length, dtype=jnp.int32)
        if idx.ndim == 0:
            q_pos = idx + steps                                 # [L]
            phys = table[:, (q_pos // bs) % ring]               # [B, L]
            off = jnp.broadcast_to((q_pos % bs)[None, :], (b, length))
        else:
            q_pos = idx[:, None] + steps[None, :]               # [B, L]
            phys = table[jnp.arange(b)[:, None], (q_pos // bs) % ring]
            off = q_pos % bs
        k_pool, v_pool = paged_kv_write(cache.k, cache.v, k_new, v_new,
                                        phys, off)
        out = None if q is None else paged_decode_attention(
            q, k_pool, v_pool, table, q_pos=q_pos, window=self.window)
        return out, cache._replace(k=k_pool, v=v_pool, index=idx + length)

    def _last_visible(self, pos):
        if self.block_length is None:
            return pos
        b = self.block_length
        return pos // b * b + (b - 1)

    def _heads(self, x, n: int):
        from ... import tensor as T

        b, l = x.shape[0], x.shape[1]
        return T.transpose(T.reshape(x, [b, l, n, self.head_dim]),
                           [0, 2, 1, 3])

    def _qkv(self, x, cache=None):
        """The projections, the heads' RMSNorm and the rotary turn at the
        positions the cache index gives (from 0 without a cache): ``q``
        ``[B, H, L, D]``, ``k``, ``v`` ``[B, Hkv, L, D]`` and the
        positions.  Shared by every token mixer built on these
        projections (``nn.PowerRetention`` replaces only the product)."""
        import jax
        import jax.numpy as jnp

        q = self._heads(self.q_proj(x), self.num_heads)
        k = self._heads(self.k_proj(x), self.num_kv_heads)
        v = self._heads(self.v_proj(x), self.num_kv_heads)
        if self.q_norm is not None:
            with jax.named_scope("qk_norm"):
                q, k = self.q_norm(q), self.k_norm(k)
        steps = jnp.arange(x.shape[1], dtype=jnp.int32)
        if cache is None:
            pos = steps
        else:
            idx = jnp.asarray(cache.index, jnp.int32)
            pos = idx + steps if idx.ndim == 0 \
                else idx[:, None] + steps[None, :]
        if self.rope_theta is not None:
            with jax.named_scope("rope"):
                q = F.rotary_embedding(q, pos, self.rope_theta)
                k = F.rotary_embedding(k, pos, self.rope_theta)
        return q, k, v, pos

    def forward(self, x, attn_mask=None, cache=None, plane=None):
        """``plane`` (with a cache of several planes,
        ``gen_decode_cache(planes=)``): the plane this call writes and
        attends, an int32 scalar that may be traced."""
        from ...framework.tensor import Tensor as _T

        if attn_mask is not None:
            raise InvalidArgumentError(
                "GroupedQueryAttention derives its mask from positions "
                "(causal, or block-causal with block_length); pass "
                "attn_mask=None")
        q, k, v, pos = self._qkv(x, cache)
        if cache is not None and self._is_prompt(q, cache, plane):
            # a prompt from position 0: written to the cache, attended
            # over its own keys (a window entry's ring may be shorter than
            # the prompt once it lies in a pool; the row it is prefilled
            # into spans it whole)
            from ...ops.flash_attention import prompt_attention

            out = prompt_attention(q.value, k.value, v.value,
                                   self.head_dim ** -0.5, self.window)
            if isinstance(cache, self.WindowDecodeCache):
                if x.shape[1] > cache.table.shape[1] * cache.k.shape[2]:
                    raise InvalidArgumentError(
                        "a prompt of %d positions into a ring of %d blocks "
                        "of %d: a window entry takes a prompt whole only "
                        "where its ring spans it (a self-managed cache, "
                        "gen_decode_cache(num_blocks=None))"
                        % (x.shape[1], cache.table.shape[1],
                           cache.k.shape[2]))
                _, cache = self._window_decode_forward(
                    None, k.value, v.value, cache)
            else:
                cache = self._write_only(k, v, cache)
            return self.out_proj(self._merge_heads(
                _T(out, stop_gradient=True))), cache
        if cache is not None:
            if isinstance(cache, self.WindowDecodeCache) and x.shape[1] > 1:
                raise InvalidArgumentError(
                    "a chunk of %d positions that starts mid-way against a "
                    "window entry (window=%d behind a ring of %d blocks): a "
                    "speculative verify chunk, a chunk of a chunked "
                    "prefill or a block of diffusion rows may ask for keys "
                    "the ring has already overwritten; only a decode step "
                    "and a prompt from position 0 are built"
                    % (x.shape[1], self.window, cache.table.shape[1]))
            fwd = (self._decode_forward
                   if isinstance(cache, self.DecodeCache)
                   else self._paged_decode_forward)
            out, cache = fwd(q, k, v, None, cache,
                             **({} if plane is None else {"plane": plane}))
            out = self.out_proj(self._merge_heads(
                _T(out, stop_gradient=True)))
            return out, cache
        from ...ops.flash_attention import decode_attention, prompt_attention

        if self.window is not None:
            out = prompt_attention(q.value, k.value, v.value,
                                   self.head_dim ** -0.5, self.window)
        else:
            # no cache: the same composition over the sequence's own K/V
            out = decode_attention(q.value, k.value, v.value,
                                   q_pos=self._last_visible(pos),
                                   route="composition")
        return self.out_proj(self._merge_heads(_T(out,
                                                  stop_gradient=True)))

    def _is_prompt(self, q, cache, plane) -> bool:
        """Whether the chunk ``q`` against ``cache`` is attended over its
        own keys: more than one position, a causal mask (no
        ``block_length``), one plane, a cache KNOWN to stand at 0 while the
        program is traced, and either a window layer (whose ring need not
        hold the prompt) or shapes the flash kernel takes."""
        from ...ops.flash_attention import prompt_flash_supported
        from .latent_attention import _starts_at_zero

        if q.shape[2] < 2 or self.block_length is not None \
                or plane is not None \
                or getattr(cache, "k_scale", None) is not None \
                or not _starts_at_zero(cache.index):
            return False
        return self.window is not None \
            or prompt_flash_supported(q.shape, q.value.dtype)

    def _write_only(self, k, v, cache):
        """A prompt's K/V rows into a dense or a paged cache at positions
        0 .. L - 1, the index advanced; nothing is attended."""
        import jax.numpy as jnp

        from ...ops.flash_attention import paged_kv_write

        length = k.shape[2]
        idx = jnp.asarray(cache.index, jnp.int32)
        if isinstance(cache, self.DecodeCache):
            upd = {f: jax.lax.dynamic_update_slice(
                getattr(cache, f), x.value.astype(getattr(cache, f).dtype),
                (0, 0, 0, 0)) for f, x in (("k", k), ("v", v))}
            return cache._replace(index=idx + length, **upd)
        bs = cache.k.shape[2]
        pos = jnp.arange(length, dtype=jnp.int32)
        table = jnp.asarray(cache.table, jnp.int32)
        phys = table[:, pos // bs]
        off = jnp.broadcast_to((pos % bs)[None, :], phys.shape)
        k_pool, v_pool = paged_kv_write(cache.k, cache.v, k.value, v.value,
                                        phys, off)
        return cache._replace(k=k_pool, v=v_pool, index=idx + length)


class GatedMLP(Layer):
    """The dense gated feed-forward of the Llama/Qwen family: ``down(
    silu(gate(x)) * up(x))``, no bias.  (``nn.SparseExperts`` is the
    routed form of the same product.)"""

    def __init__(self, hidden_size: int, intermediate_size: int):
        super().__init__()
        self.gate_proj = Linear(hidden_size, intermediate_size,
                                bias_attr=False)
        self.up_proj = Linear(hidden_size, intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _row_parallel_seam(linear, x):
    """Route one row-parallel projection (attention ``out_proj`` / MLP
    ``linear2`` — weight placed ``P('mp', None)`` by the mesh axis
    rules) through the quantized mp-collective seam when a decode trace
    installed it (``distributed.qcollectives``, docs/DESIGN.md §5r).

    Returns None when the seam is inactive OR recording-only
    (``collective_quant="none"``) — the caller then takes the plain
    Linear call, whose jaxpr is exactly what an unseamed build traces
    (byte-identity, test-pinned).  A bank-attached Linear's LoRA delta
    is re-applied on the reduced result in ``Linear.forward``'s order:
    the delta contracts the GLOBAL input against the replicated bank,
    so it rides outside the mp reduction unquantized.
    """
    from ...distributed import qcollectives as _qc

    ctx = _qc.active()
    if ctx is None:
        return None
    from ...framework.tensor import Tensor as _T

    bias = getattr(linear, "bias", None)
    out = _qc.row_parallel_linear(
        getattr(x, "value", x), linear.weight.value,
        None if bias is None else bias.value, ctx)
    if out is None:
        return None
    out = _T(out, stop_gradient=True)
    lora_a = linear._parameters.get("lora_a")
    if lora_a is not None:
        from .. import lora as _lora

        ids = _lora.current_adapter_ids()
        if ids is not None:
            out = _lora.apply_delta(out, x, lora_a,
                                    linear._parameters["lora_b"], ids)
    return out


class TransformerEncoderLayer(Layer):
    """transformer.py:398 parity; post-norm by default (normalize_before=False)."""

    def __init__(
        self,
        d_model: int,
        nhead: int,
        dim_feedforward: int,
        dropout: float = 0.1,
        activation: str = "relu",
        attn_dropout: Optional[float] = None,
        act_dropout: Optional[float] = None,
        normalize_before: bool = False,
        weight_attr=None,
        bias_attr=None,
    ):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout, weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.activation = activation

    def _act(self, x):
        return getattr(F, self.activation)(x)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        hidden = self.dropout(self._act(self.linear1(src)))
        # row-parallel seam 2 (docs §5r): the MLP down-projection's
        # mp reduction, quantized exactly like out_proj's when a decode
        # trace installed the seam; None = the dense GSPMD path
        src = _row_parallel_seam(self.linear2, hidden)
        if src is None:
            src = self.linear2(hidden)
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        return self.self_attn.gen_decode_cache(batch_size, max_length,
                                               dtype, per_slot, layout,
                                               block_size, num_blocks)


class TransformerEncoder(Layer):
    """transformer.py:622 parity."""

    def __init__(self, encoder_layer, num_layers: int, norm=None):
        super().__init__()
        from .container import LayerList

        self.layers = LayerList([encoder_layer] + [
            type(encoder_layer)(**_clone_args(encoder_layer)) for _ in range(num_layers - 1)
        ])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask)
            else:
                output, new_cache = mod(output, src_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        return [layer.gen_decode_cache(batch_size, max_length, dtype,
                                       per_slot, layout, block_size,
                                       num_blocks)
                for layer in self.layers]


class TransformerDecoderLayer(Layer):
    """transformer.py:721 parity."""

    def __init__(
        self,
        d_model: int,
        nhead: int,
        dim_feedforward: int,
        dropout: float = 0.1,
        activation: str = "relu",
        attn_dropout: Optional[float] = None,
        act_dropout: Optional[float] = None,
        normalize_before: bool = False,
        weight_attr=None,
        bias_attr=None,
    ):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout, weight_attr=weight_attr, bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout, weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.dropout3 = Dropout(dropout, mode="upscale_in_train")
        self.activation = activation

    def _act(self, x):
        return getattr(F, self.activation)(x)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incr_cache = None
        else:
            tgt, incr_cache = self.self_attn(tgt, tgt, tgt, tgt_mask, cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            if isinstance(tgt, tuple):
                tgt = tgt[0]
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self._act(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incr_cache, cache[1]))

    def gen_cache(self, memory):
        incr = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(memory, memory, type=MultiHeadAttention.StaticCache)
        return incr, static


class TransformerDecoder(Layer):
    """transformer.py:940 parity."""

    def __init__(self, decoder_layer, num_layers: int, norm=None):
        super().__init__()
        from .container import LayerList

        self.layers = LayerList([decoder_layer] + [
            type(decoder_layer)(**_clone_args(decoder_layer)) for _ in range(num_layers - 1)
        ])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask, memory_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip: bool = False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


def _clone_args(layer):
    """Rebuild constructor kwargs from a prototype encoder/decoder layer."""
    return dict(
        d_model=layer.norm1._normalized_shape[0],
        nhead=layer.self_attn.num_heads,
        dim_feedforward=layer.linear1.out_features,
        dropout=layer.dropout1.p,
        activation=layer.activation,
        attn_dropout=layer.self_attn.dropout,
        act_dropout=layer.dropout.p,
        normalize_before=layer.normalize_before,
    )


class Transformer(Layer):
    """transformer.py:1112 parity."""

    def __init__(
        self,
        d_model: int = 512,
        nhead: int = 8,
        num_encoder_layers: int = 6,
        num_decoder_layers: int = 6,
        dim_feedforward: int = 2048,
        dropout: float = 0.1,
        activation: str = "relu",
        attn_dropout=None,
        act_dropout=None,
        normalize_before: bool = False,
        weight_attr=None,
        bias_attr=None,
        custom_encoder=None,
        custom_decoder=None,
    ):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr, bias_attr,
            )
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers, enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr, bias_attr,
            )
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers, dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None, memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    def generate_square_subsequent_mask(self, length: int):
        from ... import tensor as T

        full = T.full([length, length], -1e9, dtype="float32")
        return T.triu(full, diagonal=1)
