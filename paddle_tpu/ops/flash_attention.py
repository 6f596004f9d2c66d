"""Flash attention: tiled online-softmax attention for TPU.

Reference parity: ``paddle.incubate.nn.functional.fused_multi_head_attention``
/ ``operators/fused/fused_attention_op.cu`` (one fused kernel instead of
matmul→softmax→matmul round-tripping scores through HBM).

TPU-native design: the pallas flash-attention kernel
(``jax.experimental.pallas.ops.tpu.flash_attention``) streams K/V blocks
through VMEM with an online softmax, so HBM traffic is O(L·D) instead of
O(L²) — the canonical MXU/VMEM blocking from the pallas guide.  Forward and
backward are both pallas kernels (custom_vjp built in).  ``flash_attention``
here adds the shape/backend gate and an XLA-composition fallback so the same
call works on CPU test meshes and odd shapes.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError

__all__ = ["flash_attention", "flash_attention_supported",
           "decode_attention", "decode_attention_supported",
           "paged_decode_attention", "paged_decode_attention_supported",
           "paged_cache_write", "paged_kv_write", "paged_kv_write_route",
           "quantize_kv", "dequantize_kv",
           "latent_decode_attention", "latent_cache_write",
           "causal_attention", "causal_flash_supported",
           "prompt_attention", "prompt_flash_supported",
           "decode_route", "normalize_decode_route", "DECODE_ROUTES",
           "reset_backend_memo"]

_SUPPORTED_DTYPES = (jnp.float32, jnp.bfloat16)

# Measured crossover on v5e (bf16, head_dim 64, fwd+bwd, tokens held
# constant): XLA's fused composition wins below ~4k sequence (5.2ms vs 6.7ms
# at L=512·B=16; 9.2 vs 12.1 at L=2048·B=4), the pallas kernel wins above
# (22.2 vs 19.4 at L=4096·B=2) where the O(L²) HBM scores dominate.
FLASH_MIN_SEQ = 4096


def flash_attention_supported(q_shape, dtype, dropout_p: float = 0.0) -> bool:
    """Gate: pallas kernel needs TPU, 4-D [B,H,L,D], MXU-tileable L and D,
    no attention-weight dropout (the kernel never materializes weights),
    and a sequence long enough that tiling beats XLA's fused composition."""
    if jax.default_backend() != "tpu":
        return False
    if dropout_p > 0.0:
        return False
    if len(q_shape) != 4:
        return False
    b, h, l, d = q_shape
    if l % 128 != 0 or l < FLASH_MIN_SEQ:
        return False
    # the kernel takes ONE head size for q, k and v, a whole or half
    # 128-lane tile or two; other sizes (and a value head narrower than
    # the score head) reach it zero-padded: ``causal_attention``
    if d not in (64, 128, 256):
        return False
    return jnp.dtype(dtype) in _SUPPORTED_DTYPES


def _reference_attention(q, k, v, bias, causal, sm_scale, segment_ids=None):
    scores = jnp.einsum("...qd,...kd->...qk", q, k) * jnp.asarray(
        sm_scale, q.dtype)
    if causal:
        ql, kl = scores.shape[-2], scores.shape[-1]
        allow = jnp.tril(jnp.ones((ql, kl), dtype=bool))
        scores = jnp.where(allow, scores, jnp.finfo(scores.dtype).min)
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        same = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        scores = jnp.where(same, scores, jnp.finfo(scores.dtype).min)
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    key_padding_mask=None, segment_ids=None):
    """[B, H, L, D] attention; pallas kernel on TPU, XLA fallback elsewhere.

    ``bias``: additive attention bias broadcastable to [B, H, Lq, Lk]
    (the paddle additive attn_mask convention).  Prefer the O(L) forms for
    ragged batches — they never materialize an [L, L] mask:

    ``key_padding_mask``: [B, Lk] bool, True = real token (from
    ``tensor.sequence_mask``); padded keys are excluded from every softmax.
    ``segment_ids``: ([B, Lq], [B, Lk]) int pair — attention is confined to
    positions with equal ids (packed-sequence / LoD batches, from
    ``tensor.lengths_to_segment_ids``); maps directly onto the pallas
    kernel's SegmentIds lanes.
    """
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if key_padding_mask is not None:
        if segment_ids is not None:
            raise ValueError(
                "pass either key_padding_mask or segment_ids, not both")
        # valid keys → segment 0; pads → 1.  Queries are all segment 0 (their
        # pad rows are ignored downstream), so every softmax sees only real
        # keys.  [B, L] ints instead of an [L, L] mask.
        kv_seg = jnp.where(jnp.asarray(key_padding_mask, bool), 0, 1) \
            .astype(jnp.int32)
        q_seg = jnp.zeros((q.shape[0], q.shape[2]), jnp.int32)
        segment_ids = (q_seg, kv_seg)
    elif segment_ids is not None:
        segment_ids = (jnp.asarray(segment_ids[0], jnp.int32),
                       jnp.asarray(segment_ids[1], jnp.int32))
    if not flash_attention_supported(q.shape, q.dtype):
        return _reference_attention(q, k, v, bias, causal, sm_scale,
                                    segment_ids)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        SegmentIds,
        flash_attention as _pallas_flash,
    )

    from ..core.flags import flag as _flag

    ab = None
    if bias is not None:
        b_, h_, lq, lk = q.shape[0], q.shape[1], q.shape[2], k.shape[2]
        ab = jnp.broadcast_to(bias.astype(q.dtype), (b_, h_, lq, lk))
    # FLAGS_seq_block_size bounds the kernel's sequence tiles (VMEM budget
    # knob for very long sequences); 0/default lets the kernel choose.
    blk = int(_flag("FLAGS_seq_block_size") or 0)
    block_sizes = None
    lq, lk = q.shape[2], k.shape[2]
    if blk and (blk < min(lq, lk)) and lq % blk == 0 and lk % blk == 0:
        block_sizes = BlockSizes(
            block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
            block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
            block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk,
            block_q_dq=blk)
    return _pallas_flash(q, k, v, ab=ab,
                         segment_ids=(SegmentIds(*segment_ids)
                                      if segment_ids is not None else None),
                         causal=causal,
                         sm_scale=float(sm_scale), block_sizes=block_sizes)


# ---------------------------------------------------------------------------
# int8 KV-cache quantization: per-head absmax scales
# ---------------------------------------------------------------------------

# Floor for the absmax scale: an all-zero head row (a never-written cache
# position) quantizes to zeros with a zero-ish scale instead of dividing
# by zero; any real activation dwarfs this.
KV_QUANT_EPS = 1e-8


def quantize_kv(x):
    """``[..., D]`` float K/V -> ``(int8 values [..., D], fp32 scales
    [...])`` — symmetric per-head absmax quantization, the granularity of
    the int8 KV cache: the quantization group is ONE head's ``[D]``
    vector at one position, so the scale tensor is the K/V buffer minus
    its head_dim axis (dense cache ``[B, H, S]``, paged pool
    ``[num_blocks, H, block_size]``).  Runs INSIDE the compiled
    prefill/decode step (quantize-on-write), the compiler-first
    discipline: cache dtype is a property of the program, not a host-side
    conversion pass."""
    xf = jnp.asarray(x, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), KV_QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv`: ``int8 [..., D]`` times its
    per-head ``[...]`` scales.  In the int8 decode paths this runs on
    the GATHERED rows inside the attention composition, so the HBM-side
    read of the cache is int8 and the fp up-cast happens in the fused
    kernel's registers/VMEM — the bandwidth side is where the win lives
    (EQuARX; decode is cache-bandwidth-bound)."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


# ---------------------------------------------------------------------------
# decode-time attention: one (or few) query positions against a
# preallocated KV cache
# ---------------------------------------------------------------------------

# Same measured-crossover discipline as FLASH_MIN_SEQ: a kernel only
# replaces the XLA composition where a measurement says it wins.  The
# pallas flash kernel is shape-gated to Lq % 128 == 0, so a single-query
# decode step can NEVER take it; the decode-step composition below is a
# batched GEMV + softmax + GEMV that XLA fuses into one HBM pass over the
# cache, and below this cache length no measurement has shown the fused
# pallas decode kernel (ops/pallas_decode.py) beating it.  Above it the
# "auto" route engages the kernel on TPU; ``tools/paged_kernel_bench.py
# --composition`` times the paged kernel beside the composition on the
# chip, which is the measurement that may move this constant.
DECODE_FLASH_MIN_CACHE = 16384

# -- decode routing ----------------------------------------------------
# "auto": the measured-crossover discipline — the fused pallas kernel
#   engages exactly where the ``*_supported`` gates say it wins (TPU
#   backend, short chunk, MXU-tileable head_dim, cache past the
#   crossover); everything else takes the XLA composition.
# "composition": force the gather+dequant+attention composition.
# "pallas": force the fused kernel on every decode-sized chunk
#   (Lq <= 8) — off-TPU it runs under the pallas INTERPRETER, which is
#   how tier-1 tests pin numeric identity on CPU.  The bucketed
#   prefill's long chunk keeps the composition, so a forced session
#   still prefills.
DECODE_ROUTES = ("auto", "composition", "pallas")

# The ambient route is THREAD-LOCAL (the repo's convention for ambient
# trace state — core/amp_state.py, core/random.py): the serving
# engine's loop thread traces its executables under its own route
# while the main thread may be warming another session, and a shared
# stack would let one thread pop the other's entry mid-trace.
_route_state = threading.local()


def _route_stack() -> list:
    stack = getattr(_route_state, "stack", None)
    if stack is None:
        stack = _route_state.stack = ["auto"]
    return stack


def normalize_decode_route(route) -> str:
    """Validated route name, or a typed error naming the choices —
    checked at session/pool construction AND at every explicit
    ``route=`` call site, so a typo'd route fails loudly instead of
    silently decoding on the wrong path."""
    if route not in DECODE_ROUTES:
        raise InvalidArgumentError(
            "decode route must be one of %s, got %r"
            % (list(DECODE_ROUTES), route))
    return route


@contextlib.contextmanager
def decode_route(route):
    """Ambient decode-attention routing for a trace region: the decode
    sessions wrap their model forwards in this so the ``route=`` knob
    reaches the attention ops buried under the layer stack without
    threading a kwarg through every ``forward``.  The route is
    PYTHON-static — it selects which ops get traced, so a session's
    executables are compiled for exactly one path and the compile-count
    contract is untouched."""
    stack = _route_stack()
    stack.append(normalize_decode_route(route))
    try:
        yield
    finally:
        stack.pop()


# jax.default_backend() walks the backend registry on every call; the
# decode gates run on EVERY trace of every decode-family executable, so
# the lookup is memoized at module level (the backend cannot change
# within a process once jax initializes).  ``reset_backend_memo`` is
# the test hook for monkeypatched backends.
_backend_memo: Optional[str] = None


def _cached_backend() -> str:
    global _backend_memo
    if _backend_memo is None:
        _backend_memo = jax.default_backend()
    return _backend_memo


def reset_backend_memo() -> None:
    global _backend_memo
    _backend_memo = None


def _kernel_refusal(q_shape, dtype, tile, mosaic):
    """Why the fused kernel cannot take this call, or None — what
    ``route='pallas'`` may force.  Structure first (4-D float queries),
    then, when the kernel would be COMPILED (TPU backend), Mosaic's
    layout rules (``mosaic()``: ``pallas_decode.mosaic_refusal`` for
    the dense kernel, ``paged_mosaic_refusal`` for the paged one); the
    interpreter has no layout to refuse.  The crossover conditions live
    in the ``*_supported`` gates — they decide WINNING, this decides
    EXISTING."""
    if len(q_shape) != 4:
        return "queries must be 4-D [B, H, Lq, D], got %r" % (
            tuple(q_shape),)
    if jnp.dtype(dtype) not in _SUPPORTED_DTYPES:
        return "query dtype %s is not float32/bfloat16" % (
            jnp.dtype(dtype).name,)
    if _cached_backend() != "tpu" and tile is not None:
        return None
    return mosaic()


def _bias_kernel_compatible(bias, b, h, lq, s) -> bool:
    """The kernel streams bias block-wise and needs the materialized
    4-D [B|1, H|1, Lq, S] layout; other broadcastable shapes keep the
    composition (the transformer decode paths pass ``q_pos`` instead of
    a bias, so this only ever gates external callers).  The shape rule
    itself lives with the kernel (``bias_streamable``) so routing and
    kernel validation cannot diverge."""
    if bias is None:
        return True
    from .pallas_decode import bias_streamable

    return bias_streamable(getattr(bias, "shape", ()), b, h, lq, s)


def _resolve_route(route, q_shape, supported: bool, refusal) -> bool:
    """True when this call takes the fused pallas kernel.  ``refusal``
    is ``_kernel_refusal``'s answer: under a forced route a refused
    decode-sized chunk raises its reason — it never decodes on the
    composition behind the caller's back."""
    from .pallas_decode import MAX_KERNEL_QUERY_CHUNK

    r = _route_stack()[-1] if route is None \
        else normalize_decode_route(route)
    if r == "composition":
        return False
    if r == "auto":
        return supported
    if len(q_shape) == 4 and q_shape[2] > MAX_KERNEL_QUERY_CHUNK:
        return False  # prefill-shaped chunk: the composition by design
    if refusal is not None:
        raise InvalidArgumentError(
            "route='pallas' cannot run this decode step on the fused "
            "kernel: %s" % (refusal,))
    return True


def _qpos_bias(q_pos, s_len: int, dtype):
    """The composition's additive mask from last-visible-key positions:
    [L] q_pos -> [1, 1, L, S] (aligned batch), [B, L] -> [B, 1, L, S]
    (slot-batched) — op-for-op the mask the transformer decode paths
    built inline before the routing seam existed, so the composition's
    jaxpr (and its compiled output) is unchanged."""
    qp = jnp.asarray(q_pos, jnp.int32)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, dtype)
    if qp.ndim == 1:
        allow = jnp.arange(s_len)[None, :] <= qp[:, None]
        return jnp.where(allow, 0.0, neg)[None, None]
    allow = jnp.arange(s_len)[None, None, :] <= qp[:, :, None]
    return jnp.where(allow, 0.0, neg)[:, None]


def _band_bias(q_pos, key_pos, window: int, dtype):
    """The composition's additive mask of a WINDOW layer: query ``i`` sees
    key ``j`` where ``i - window < j <= i``, the ``window`` positions that
    end at its own.  ``q_pos`` ``[L]`` or ``[B, L]`` as ``_qpos_bias``
    takes it; ``key_pos`` the position each key of the score axis holds,
    ``[S]`` (keys in order: a dense cache, a prompt's own) or ``[B, S]``
    (a ring's blocks as gathered; negative: nothing written there yet)."""
    qp = jnp.asarray(q_pos, jnp.int32)
    kp = jnp.asarray(key_pos, jnp.int32)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, dtype)
    if qp.ndim == 1 and kp.ndim == 1:
        qp, kp = qp[:, None], kp[None, :]                       # [L, S]
        lead = (None, None)
    else:
        qp = (qp[None] if qp.ndim == 1 else qp)[:, :, None]     # [B, L, 1]
        kp = (kp[None] if kp.ndim == 1 else kp)[:, None, :]     # [B, 1, S]
        lead = (slice(None), None)
    allow = (kp <= qp) & (kp > qp - window) & (kp >= 0)
    return jnp.where(allow, 0.0, neg)[lead]


def _effective_qpos(q_pos, lengths, b: int, lq: int, s: int):
    """The kernel's [B, Lq] mask-index form of whatever masking the
    caller expressed: ``q_pos`` (per-query last visible key) and/or
    ``lengths`` (valid-token counts; key s is visible iff s < lengths,
    i.e. last visible = lengths - 1), combined by min.  With neither,
    every key is visible."""
    qp = None
    if q_pos is not None:
        qp = jnp.asarray(q_pos, jnp.int32)
        if qp.ndim == 1:
            qp = jnp.broadcast_to(qp[None, :], (b, lq))
        else:
            qp = jnp.broadcast_to(qp, (b, lq))
    if lengths is not None:
        ln = jnp.asarray(lengths, jnp.int32)
        if ln.ndim == 0:
            ln = jnp.broadcast_to(ln[None], (b,))
        lim = jnp.broadcast_to((ln - 1)[:, None], (b, lq))
        qp = lim if qp is None else jnp.minimum(qp, lim)
    if qp is None:
        qp = jnp.full((b, lq), s - 1, jnp.int32)
    return qp


def fold_query_groups(q, kv_heads: int, q_pos=None):
    """Grouped K/V heads: query head ``n`` reads K/V head ``n // g`` with
    ``g = Hq / Hkv``, so the ``g`` query heads of one K/V head and their
    ``Lq`` positions are ONE block of ``g * Lq`` rows against that head's
    keys.  ``q`` [B, Hq, Lq, D] -> [B, Hkv, g * Lq, D] (row ``i * Lq + l``
    is query head ``n = kv * g + i`` at chunk position ``l``) and ``q_pos``
    ([Lq] or [B, Lq]) tiled to match.  Returns ``(q, q_pos)``; undo with
    ``out.reshape(B, Hq, Lq, D)``."""
    b, hq, lq, d = q.shape
    if kv_heads < 1 or hq % kv_heads:
        raise InvalidArgumentError(
            "query heads %d are not a whole multiple of the cache's K/V "
            "heads %d" % (hq, kv_heads))
    g = hq // kv_heads
    if q_pos is not None:
        qp = jnp.asarray(q_pos, jnp.int32)
        q_pos = jnp.tile(qp, (g,) if qp.ndim == 1 else (1, g))
    return q.reshape(b, kv_heads, g * lq, d), q_pos


def decode_attention_supported(q_shape, kv_len: int, dtype) -> bool:
    """Gate for the fused single-query/short-chunk pallas decode kernel
    (``ops.pallas_decode.decode_attention_kernel``): TPU backend, 4-D
    [B, H, Lq, D] with a short query chunk, a cache long enough to beat
    the fused XLA composition, and a geometry Mosaic compiles
    (``mosaic_refusal``: head_dim, and a bounded sequence tile of whole
    sublanes).  This is the "auto" route's decision;
    ``route="pallas"``/``"composition"`` override it for tests and
    sweeps."""
    from .pallas_decode import (MAX_KERNEL_QUERY_CHUNK, dense_seq_block,
                                mosaic_refusal)

    if _cached_backend() != "tpu":
        return False
    if len(q_shape) != 4 or q_shape[2] > MAX_KERNEL_QUERY_CHUNK:
        return False
    if kv_len < DECODE_FLASH_MIN_CACHE:
        return False
    if jnp.dtype(dtype) not in _SUPPORTED_DTYPES:
        return False
    return mosaic_refusal(q_shape[3], dense_seq_block(kv_len),
                          kv_len) is None


def decode_attention(q, k, v, bias=None, sm_scale: Optional[float] = None,
                     k_scale=None, v_scale=None, q_pos=None, route=None,
                     score_dtype=None, window: Optional[int] = None):
    """Decode-step attention: [B, H, Lq, D] queries against a FULL
    preallocated cache [B, H, S, D] (S = max_len), with ``bias`` masking
    the invalid tail (positions at or beyond the cache index) to -inf.

    Lq is the current chunk: 1 for autoregressive decode, spec_k+1 for
    a speculative VERIFY step (jit/speculative.py) — the verify chunk
    reuses this composition unchanged, which is why speculative logits
    equal plain decode logits up to reduction order, and why the
    single-query kernel gate below admits short chunks (Lq <= 8), not
    just Lq == 1.  The math is deliberately identical to the XLA
    fallback in
    ``F.scaled_dot_product_attention`` so cached and uncached logits
    agree to float-reduction noise.  Masked (garbage) cache positions
    contribute exp(-inf) == 0 to the softmax, so preallocation never
    changes the result, only the reduction shape — which XLA keeps
    shape-static across every decode step.

    ``k_scale``/``v_scale`` ([B, H, S] fp32) mark an int8-quantized
    cache: K/V arrive as int8 and are dequantized per head IN the
    composition (the HBM read is int8; the up-cast fuses into the score
    matmul).  The sm_scale default keys off the QUERY's head_dim, so the
    int8 path scores identically to fp32 up to quantization error.

    ``q_pos`` ([Lq] or [B, Lq] int32) expresses the causal-prefix mask
    as the last key position each query may attend — the structured
    form the decode-cache forwards pass so the fused kernel route can
    mask in-register instead of streaming a materialized bias; the
    composition builds the exact additive mask the callers used to
    build inline.  ``route`` overrides the ambient :func:`decode_route`
    ("auto" | "composition" | "pallas").  ``score_dtype`` (composition
    only; None keeps the queries' type) is the type the scores and the
    softmax are taken in: the grouped-head callers pass float32, as the
    fused kernel computes, so that a bfloat16 model's attention does not
    round its probabilities to eight bits.

    ``window`` (with ``q_pos``): a WINDOW layer's band, a query sees keys
    ``q_pos - window < j <= q_pos``.  The composition only: a dense cache
    keeps every position and the band is its mask's lower edge."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if window is not None:
        if q_pos is None or bias is not None:
            raise InvalidArgumentError(
                "a window's band is taken from q_pos, without a bias")
        if q.shape[1] != k.shape[1]:
            q_f, q_pos = fold_query_groups(q, k.shape[1], q_pos)
        else:
            q_f = q
        out = decode_attention(
            q_f, k, v, bias=_band_bias(q_pos, jnp.arange(k.shape[2]),
                                       int(window), jnp.float32),
            sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale,
            route="composition", score_dtype=jnp.float32)
        return out.reshape(q.shape)
    if q.ndim == 4 and k.ndim == 4 and q.shape[1] != k.shape[1]:
        # grouped K/V heads: the heads that share a K/V head fold into
        # the chunk axis, and the chunk is then past the kernel's
        # length, so a dense cache of K/V heads takes the composition
        if bias is not None:
            raise InvalidArgumentError(
                "grouped K/V heads take their mask as q_pos, not as an "
                "additive bias")
        qf, qp = fold_query_groups(q, k.shape[1], q_pos)
        out = decode_attention(qf, k, v, sm_scale=sm_scale,
                               k_scale=k_scale, v_scale=v_scale, q_pos=qp,
                               route="composition",
                               score_dtype=jnp.float32)
        return out.reshape(q.shape)
    s = k.shape[2]
    from .pallas_decode import dense_seq_block, mosaic_refusal

    if _resolve_route(
            route, q.shape,
            decode_attention_supported(q.shape, s, q.dtype)
            and _bias_kernel_compatible(bias, q.shape[0], q.shape[1],
                                        q.shape[2], s),
            _kernel_refusal(q.shape, q.dtype, dense_seq_block(s),
                            lambda: mosaic_refusal(
                                d, dense_seq_block(s), s,
                                bias is not None))):
        # fused pallas route (docs/DESIGN.md §5l): stream cache tiles
        # through VMEM with an online softmax — int8 tiles dequantize
        # in VMEM, so the HBM read stays int8 and the gathered fp32
        # cache is never materialized
        from .pallas_decode import decode_attention_kernel

        qp = _effective_qpos(q_pos, None, q.shape[0], q.shape[2], s)
        return decode_attention_kernel(
            q, k, v, qp, float(sm_scale), k_scale=k_scale,
            v_scale=v_scale, bias=bias,
            interpret=_cached_backend() != "tpu")
    if k_scale is not None:
        k = dequantize_kv(k, k_scale, q.dtype)
    if v_scale is not None:
        v = dequantize_kv(v, v_scale, q.dtype)
    if q_pos is not None:
        pos_bias = _qpos_bias(q_pos, s, q.dtype)
        bias = pos_bias if bias is None else bias + pos_bias
    if score_dtype is None:
        scores = jnp.einsum("...qd,...kd->...qk", q, k) * jnp.asarray(
            sm_scale, q.dtype)
    else:
        scores = jnp.einsum("...qd,...kd->...qk", q, k,
                            preferred_element_type=score_dtype) * sm_scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    weights = jax.nn.softmax(scores, axis=-1)
    if score_dtype is not None:
        weights = weights.astype(q.dtype)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


# ---------------------------------------------------------------------------
# paged decode attention: block-table KV cache (vLLM scheme, static shapes)
# ---------------------------------------------------------------------------


def paged_decode_attention_supported(q_shape, block_size: int,
                                     num_blocks: int, dtype,
                                     planes: int = 1) -> bool:
    """Gate for the fused pallas PAGED decode kernel
    (``ops.pallas_decode.paged_decode_attention_kernel``), mirroring
    ``decode_attention_supported``: TPU backend, short query chunk, a
    pool big enough that the hand-tiled gather kernel beats the XLA
    gather+composition, and a geometry Mosaic compiles
    (``paged_mosaic_refusal``: a head_dim of whole lanes, a
    ``block_size`` of whole sublanes; an int8 pool's scales and a bias
    add no rule).  The "auto" route's decision;
    ``route="pallas"``/``"composition"`` override it.  A pool of several
    ``planes`` (``paged_decode_attention``'s ``head_base``) is as big as
    the positions of all its planes: each is walked by a call of its own
    against the same table."""
    from .pallas_decode import MAX_KERNEL_QUERY_CHUNK, paged_mosaic_refusal

    if _cached_backend() != "tpu":
        return False
    if len(q_shape) != 4 or q_shape[2] > MAX_KERNEL_QUERY_CHUNK:
        return False
    if block_size * num_blocks * planes < DECODE_FLASH_MIN_CACHE:
        return False
    if jnp.dtype(dtype) not in _SUPPORTED_DTYPES:
        return False
    return paged_mosaic_refusal(q_shape[3], block_size) is None


def paged_cache_write(pool, new, phys, off, head_base=None):
    """Write a chunk's rows into a block pool where the pool lies.

    ``pool``: ``[num_blocks, H, block_size, D]`` K or V pool, or the
    ``[num_blocks, H, block_size]`` scale pool of an int8 cache.
    ``new``: the chunk, ``[B, H, L, D]`` (scales ``[B, H, L]``), cast to
    the pool's dtype.  ``phys``/``off``: ``[B, L]`` int32, the physical
    block and the offset inside it of row ``b``'s position ``l``.
    Returns the pool with ``pool[phys[b, l], h, off[b, l]] = new[b, h,
    l]`` for every ``(b, h, l)``.

    Every dimension but the minor-most is INDEXED, so the scatter's
    window is ``D`` alone (empty for a scale pool).  A TPU scatter wants
    its window dimensions minor-most: ``D`` is that already in the
    row-major layout the fused kernel (``_paged_call``) is pinned to,
    so the donated pool is updated in place and handed on as it lies.
    Leaving ``H`` as a window dimension (``pool.at[phys, :, off, :]``)
    makes layout assignment want ``{3,1,2,0}`` and copy the whole pool
    there and back for sixteen rows (docs/DESIGN.md §5b).

    An index outside the pool is DROPPED, never clamped onto a live
    block.  Indices may repeat (inactive slots all write the scratch
    block, several at one offset): which row lands there is not
    defined, and nothing reads it.

    ``head_base`` (an int32 scalar, traced or not): the pool holds several
    PLANES of ``new.shape[1]`` heads side by side on its head axis (a
    stack run several times keeps one plane a pass,
    ``models.LoopedLM``), and the chunk goes to heads ``head_base ...``:
    the same scatter, the head index offset, nothing of a plane's size
    read or copied."""
    if head_base is None:
        heads = jnp.arange(pool.shape[1], dtype=jnp.int32)
    else:
        heads = jnp.asarray(head_base, jnp.int32) \
            + jnp.arange(new.shape[1], dtype=jnp.int32)
    with jax.named_scope("cache_write"):
        return pool.at[phys[..., None], heads, off[..., None]].set(
            jnp.moveaxis(new, 1, 2).astype(pool.dtype), mode="drop")


def paged_kv_write_route(pool, chunk: int, route=None) -> str:
    """``"kernel"`` or ``"scatter"``: how a chunk of ``chunk`` positions
    is written into the K and V pools shaped and typed as ``pool`` (an
    array or a ``ShapeDtypeStruct``, ``[num_blocks, H, block_size, D]``).
    Decided from what the call can see, as the attention's route is
    (``_resolve_route``): under ``composition`` the scatter; under
    ``pallas`` the kernel, or its refusal raised
    (``pallas_decode.kv_write_mosaic_refusal``); under ``auto`` the kernel
    on a TPU where the geometry compiles.  Two cases keep the scatter
    under every route, by design: a prefill-shaped chunk (more than
    ``MAX_KERNEL_QUERY_CHUNK`` positions: its rows are many and its index
    rows few a row), and an int8 pool (its ``[num_blocks, H,
    block_size]`` scale pools have no ``D`` window to copy and are
    scattered whatever is done, so all four writes stay one kind).
    ``GenerationPool`` puts the answer in ``tick.decode``'s meta
    (``kv_write``)."""
    from .pallas_decode import (MAX_KERNEL_QUERY_CHUNK,
                                kv_write_mosaic_refusal)

    _, _, bs, d = pool.shape
    dtype = jnp.dtype(pool.dtype)
    if chunk > MAX_KERNEL_QUERY_CHUNK or dtype not in _SUPPORTED_DTYPES:
        return "scatter"
    on_tpu = _cached_backend() == "tpu"
    refusal = kv_write_mosaic_refusal(d, bs, dtype.itemsize) \
        if on_tpu else None
    return "kernel" if _resolve_route(
        route, (1, 1, chunk, d), on_tpu and refusal is None,
        refusal) else "scatter"


def paged_kv_write(k_pool, v_pool, k_new, v_new, phys, off, head_base=None,
                   route=None):
    """A chunk's K and V rows into their block pools, where the pools
    lie: ``paged_cache_write``'s result for each, to the bit, as
    ``(k_pool, v_pool)``.  A decode-sized chunk into float pools goes by
    ONE in-place kernel for both pools
    (``pallas_decode.paged_kv_write_kernel``: a scatter of one index row
    a (slot, head) costs the same whatever the row's bytes, and a decode
    step made two a layer); anything else by the two scatters
    (``paged_kv_write_route`` decides, from shapes, types and the ambient
    ``decode_route``)."""
    if paged_kv_write_route(k_pool, k_new.shape[2], route) == "kernel":
        from .pallas_decode import paged_kv_write_kernel

        return paged_kv_write_kernel(
            k_pool, v_pool, k_new, v_new, phys, off,
            interpret=_cached_backend() != "tpu", head_base=head_base)
    return (paged_cache_write(k_pool, k_new, phys, off, head_base),
            paged_cache_write(v_pool, v_new, phys, off, head_base))


def paged_decode_attention(q, k_pool, v_pool, table, lengths=None, bias=None,
                           sm_scale: Optional[float] = None,
                           k_scale=None, v_scale=None, q_pos=None,
                           route=None, score_dtype=None, head_base=None,
                           plane_heads: Optional[int] = None,
                           window: Optional[int] = None):
    """Decode-step attention against a BLOCK-TABLE KV cache.

    ``q``: [B, H, Lq, D] queries (Lq = 1 for autoregressive decode,
    spec_k+1 for a speculative verify chunk — same reuse discipline as
    ``decode_attention``).
    ``k_pool``/``v_pool``: [num_blocks, H, block_size, D] global block
    pools shared by every row.  ``table``: [B, max_blocks] int32 — row
    b's logical block j lives in physical pool row ``table[b, j]``
    (physical block 0 is by convention a scratch/trash block that
    unmapped logical blocks point at).  ``lengths``: optional scalar or
    [B] int32 count of VALID tokens per row; positions at or beyond it
    are masked to -inf.  ``bias`` is an extra additive mask
    broadcastable to [B, H, Lq, S] with S = max_blocks * block_size
    (callers that already know their causal-prefix mask pass it here and
    skip ``lengths``).

    ``k_scale``/``v_scale`` ([num_blocks, H, block_size] fp32) mark an
    int8-quantized pool: the per-head scales RIDE WITH their blocks
    (gathered through the same table, so a remapped block carries its
    own scales) and dequantization happens on the gathered rows — the
    pool read stays int8.

    All shapes are static — only the TABLE VALUES vary per step — so one
    XLA compilation serves every allocation state, the same
    compiler-first caching discipline as the dense ``decode_attention``
    (which this reduces to after the gather: the math is shared so paged
    and dense logits agree to float-reduction noise).  The pool rows a
    step can READ are exactly the mapped blocks, so cache HBM scales
    with allocated tokens, not max_len × rows.

    ``q_pos``/``route`` as in :func:`decode_attention`.  On the fused
    pallas route the gather below never happens: the kernel's grid
    walks the table itself (scalar-prefetched block indices feed the
    DMA), streams pool blocks into VMEM, dequantizes int8 rows there,
    and runs the online softmax — so the composition's HBM-materialized
    [B, H, S, D] gathered (and, for int8, fp32-up-cast) K/V is exactly
    the traffic the kernel deletes.

    ``head_base`` / ``plane_heads``: the pools hold several PLANES of
    ``plane_heads`` K/V heads side by side on their head axis and the
    queries attend the plane that starts at head ``head_base`` (an int32
    scalar, traced: the pass index of ``models.LoopedLM`` times the
    plane's heads).  The kernel takes the offset as one more scalar and
    copies that plane's heads of an entry; the composition gathers them
    alone.  A float pool only.

    ``window``: a WINDOW entry (``jit.cache.WindowLayout``).  ``table``
    ``[B, ring]`` is a RING: position ``p`` lives at entry ``(p // bs) %
    ring``, a block behind the window is overwritten by a later one, and a
    query sees keys ``q_pos - window < j <= q_pos``.  The kernel (one
    query a row) walks from the entry of ``q_pos - window + 1`` to the
    entry of ``q_pos``; the composition gathers the ring and takes the band
    as a bias from the position each ring place holds under the row's last
    query (any chunk whose window the ring still holds whole: the caller
    answers for that).  A float pool, ``q_pos`` and no bias.
    """
    from .pallas_decode import (paged_decode_attention_kernel,
                                paged_mosaic_refusal)

    b, mb = table.shape
    nb, h, bs, d = k_pool.shape
    if window is not None:
        if (head_base is not None or k_scale is not None or bias is not None
                or lengths is not None or q_pos is None):
            raise InvalidArgumentError(
                "a window entry is a float pool of one plane attended "
                "under q_pos alone (no lengths, no additive bias)")
        return _windowed_paged_attention(
            q, k_pool, v_pool, jnp.asarray(table, jnp.int32), q_pos,
            int(window), float(1.0 / np.sqrt(q.shape[-1])
                               if sm_scale is None else sm_scale), route)
    planed = head_base is not None
    if planed:
        if k_scale is not None or bias is not None:
            raise InvalidArgumentError(
                "a pool of several K/V planes is a float pool attended "
                "without an additive bias")
        h = int(plane_heads)
        head_base = jnp.asarray(head_base, jnp.int32)
        plane = {"head_base": head_base, "plane_heads": h}
    else:
        plane = {}
    s = mb * bs
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    grouped = q.shape[1] != h
    if grouped and bias is not None:
        raise InvalidArgumentError(
            "grouped K/V heads take their mask as q_pos/lengths, not as "
            "an additive bias")
    if _resolve_route(
            route, q.shape,
            paged_decode_attention_supported(
                q.shape, bs, nb, q.dtype,
                **({"planes": k_pool.shape[1] // h} if planed else {}))
            and _bias_kernel_compatible(bias, b, q.shape[1], q.shape[2],
                                        s),
            _kernel_refusal(q.shape, q.dtype, bs,
                            lambda: paged_mosaic_refusal(d, bs))):
        qp = _effective_qpos(q_pos, lengths, b, q.shape[2], s)
        return paged_decode_attention_kernel(
            q, k_pool, v_pool, jnp.asarray(table, jnp.int32), qp,
            float(sm_scale), k_scale=k_scale, v_scale=v_scale,
            bias=bias, interpret=_cached_backend() != "tpu", **plane)
    if grouped:
        # the composition on the folded rows: [B, Hkv, g * Lq, D]
        # against the gathered [B, Hkv, S, D], the mask tiled per head
        qf, qp = fold_query_groups(
            q, h, _effective_qpos(q_pos, lengths, b, q.shape[2], s))
        out = paged_decode_attention(
            qf, k_pool, v_pool, table, sm_scale=sm_scale, k_scale=k_scale,
            v_scale=v_scale, q_pos=qp, route="composition",
            score_dtype=jnp.float32, **plane)
        return out.reshape(q.shape)
    # gather the row's blocks: [B, MB, H, bs, D] -> [B, H, MB*bs, D];
    # XLA lowers the fancy-index to one gather over the pool's leading
    # axis, the only data-dependent op in the step
    tbl = jnp.asarray(table, jnp.int32)
    if planed:
        # the attended plane's heads of the row's blocks, and no other's
        at = (tbl[:, :, None], head_base + jnp.arange(h, dtype=jnp.int32))
    else:
        at = tbl
    k = k_pool[at].transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    v = v_pool[at].transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    ks = vs = None
    if k_scale is not None:
        ks = k_scale[tbl].transpose(0, 2, 1, 3).reshape(b, h, s)
    if v_scale is not None:
        vs = v_scale[tbl].transpose(0, 2, 1, 3).reshape(b, h, s)
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        if lengths.ndim == 0:
            allow = (jnp.arange(s) < lengths)[None, None, None, :]
        else:
            allow = (jnp.arange(s)[None, :]
                     < lengths[:, None])[:, None, None, :]
        neg = jnp.asarray(jnp.finfo(jnp.float32).min, q.dtype)
        len_bias = jnp.where(allow, 0.0, neg)
        bias = len_bias if bias is None else bias + len_bias
    if q_pos is not None:
        pos_bias = _qpos_bias(q_pos, s, q.dtype)
        bias = pos_bias if bias is None else bias + pos_bias
    # route pinned to the composition: the kernel decision was made
    # above on the PAGED shapes — re-routing the gathered dense arrays
    # would run the dense kernel on K/V already materialized in HBM,
    # the exact traffic the kernel exists to avoid
    return decode_attention(q, k, v, bias=bias, sm_scale=sm_scale,
                            k_scale=ks, v_scale=vs, route="composition",
                            score_dtype=score_dtype)


def ring_blocks_held(top, ring: int):
    """The logical block each of a ring's ``ring`` places holds once block
    ``top`` (an int32 scalar, or ``[B]``) is the last one written: place
    ``c`` holds the last block at or under ``top`` that is ``c`` modulo the
    ring; negative where nothing has been written there yet.  ``[ring]`` or
    ``[B, ring]``."""
    top = jnp.asarray(top, jnp.int32)[..., None]
    return top - (top - jnp.arange(ring, dtype=jnp.int32)) % ring


def _windowed_paged_attention(q, k_pool, v_pool, table, q_pos, window: int,
                              sm_scale: float, route):
    """``paged_decode_attention`` against a window entry's ring (its
    docstring has the contract)."""
    from .pallas_decode import (paged_decode_attention_kernel,
                                paged_mosaic_refusal)

    b, ring = table.shape
    nb, h, bs, d = k_pool.shape
    lq = q.shape[2]
    qp = _effective_qpos(q_pos, None, b, lq, ring * bs)
    # the kernel's walk takes one query a row: a longer chunk (no cell
    # runs one) is the composition's under every route
    if lq == 1 and _resolve_route(
            route, q.shape,
            paged_decode_attention_supported(q.shape, bs, nb, q.dtype),
            _kernel_refusal(q.shape, q.dtype, bs,
                            lambda: paged_mosaic_refusal(d, bs))):
        return paged_decode_attention_kernel(
            q, k_pool, v_pool, table, qp, sm_scale,
            interpret=_cached_backend() != "tpu", window=window)
    # the ring as it lies: [B, ring, H, bs, D] -> [B, H, ring * bs, D],
    # and the position each place holds under the row's top block
    # (negative: not written yet)
    k = k_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, ring * bs, d)
    v = v_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, ring * bs, d)
    block = ring_blocks_held(jnp.maximum(jnp.max(qp, axis=1), 0) // bs,
                             ring)                               # [B, ring]
    key_pos = (block[:, :, None] * bs
               + jnp.arange(bs, dtype=jnp.int32)).reshape(b, ring * bs)
    key_pos = jnp.where(jnp.repeat(block, bs, axis=1) < 0, -1, key_pos)
    qf, qpf = fold_query_groups(q, h, qp)
    out = decode_attention(
        qf, k, v, bias=_band_bias(qpf, key_pos, window, jnp.float32),
        sm_scale=sm_scale, route="composition", score_dtype=jnp.float32)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# a prompt's own attention under grouped heads, causal or banded
# ---------------------------------------------------------------------------

# the splash kernel's tiles: queries and keys by 512, as the latent
# model's flash prefill (docs 5w)
_PROMPT_FLASH_BLOCK = 512


def prompt_flash_supported(q_shape, dtype) -> bool:
    """Whether ``prompt_attention`` takes the splash kernel: a TPU, ``[B,
    H, L, D]`` with ``L`` whole tiles of ``_PROMPT_FLASH_BLOCK`` and at
    least ``CAUSAL_FLASH_MIN_SEQ``, a head of whole 128-lane tiles."""
    if _cached_backend() != "tpu" or len(q_shape) != 4:
        return False
    l, d = q_shape[2], q_shape[3]
    return (l % _PROMPT_FLASH_BLOCK == 0 and l >= CAUSAL_FLASH_MIN_SEQ
            and d % 128 == 0 and jnp.dtype(dtype) in _SUPPORTED_DTYPES)


@functools.lru_cache(maxsize=64)
def _splash_kernel(length: int, group: int, window: Optional[int],
                   block: int, interpret: bool):
    """The splash kernel of one K/V head's ``group`` query heads over
    ``length`` positions, causal (``window`` None) or a causal band.  Its
    mask is processed block by block on the host, once a shape (0.3-0.7 s
    at 12,288 positions: kept), into the lists of blocks a row of tiles
    visits: a tile outside the band is neither fetched nor computed."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk, splash_attention_mask as _sm)

    one = _sm.CausalMask((length, length)) if window is None else \
        _sm.LocalMask((length, length), (window - 1, 0), 0)
    # (built outside whatever trace asks for it: the kernel object holds
    # the mask's block lists as arrays and outlives the trace)
    with jax.ensure_compile_time_eval():
        return _sk.make_splash_mqa_single_device(
            _sm.MultiHeadMask([one] * group),
            block_sizes=_sk.BlockSizes(block_q=block, block_kv=block,
                                       block_kv_compute=block),
            interpret=interpret)


def prompt_attention(q, k, v, sm_scale: float, window: Optional[int] = None):
    """A prompt's attention over its OWN keys under grouped heads: ``q``
    ``[B, Hq, L, D]``, ``k``, ``v`` ``[B, Hkv, L, D]``, positions 0 ..
    ``L - 1``, query head ``n`` on K/V head ``n // (Hq / Hkv)``; causal, or
    with ``window`` the band ``i - window < j <= i``.  Result ``[B, Hq, L,
    D]``.

    From ``CAUSAL_FLASH_MIN_SEQ`` positions up on a TPU the splash kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention``): the scores
    never exist in HBM (28 heads x 12,288^2 float32 would be 16.9 GB), a
    K/V head is read by its group without being repeated, and a tile
    outside the band is skipped, so a window layer's prompt costs ``L x
    window`` and not ``L^2 / 2``.  Chosen over ``causal_attention``'s
    flash kernel because that one knows no band (a bias would be the
    ``L x L`` array again) and takes K/V repeated to the query heads.
    Below, and off the TPU, the XLA composition with the band in its bias
    (``prompt_flash_supported`` decides; off the TPU the kernel would run
    under the interpreter)."""
    b, hq, length, d = q.shape
    hkv = k.shape[1]
    with jax.named_scope("prefill_attn"), jax.named_scope(
            "causal" if window is None else "band"):
        if not prompt_flash_supported(q.shape, q.dtype):
            pos = jnp.arange(length, dtype=jnp.int32)
            if window is None:
                return decode_attention(q, k, v, q_pos=pos,
                                        sm_scale=sm_scale,
                                        route="composition")
            return decode_attention(q, k, v, q_pos=pos, sm_scale=sm_scale,
                                    window=window)
        if hq % hkv:
            raise InvalidArgumentError(
                "query heads %d are not a whole multiple of the K/V heads "
                "%d" % (hq, hkv))
        block = min(_PROMPT_FLASH_BLOCK, length)
        kernel = _splash_kernel(length, hq // hkv, window, block,
                                _cached_backend() != "tpu")
        # the kernel has no scale of its own: it rides the queries
        qs = (q * jnp.asarray(sm_scale, q.dtype)).reshape(
            b, hkv, hq // hkv, length, d)
        out = jax.vmap(jax.vmap(kernel))(qs, k, v)
        return out.reshape(q.shape).astype(q.dtype)


# ---------------------------------------------------------------------------
# latent attention (docs/DESIGN.md section 5w)
# ---------------------------------------------------------------------------

# The shortest prompt chunk whose causal attention takes the flash kernel
# where score and value head sizes differ.  Unlike ``FLASH_MIN_SEQ`` this is
# no crossover of speed: 64 heads x 2,048 x 2,048 float32 scores are 1.07 GB
# and at 8,192 positions 17 GB, so the composition does not exist there.
CAUSAL_FLASH_MIN_SEQ = 1024
_FLASH_HEAD_DIMS = (64, 128, 256)
# the flash kernel's tiles at a padded head size of 256 (docs 5w)
_CAUSAL_FLASH_BLOCK = 512


def causal_flash_supported(q_shape, v_dim: int, dtype) -> bool:
    """Whether ``causal_attention`` takes the pallas flash kernel: a TPU,
    ``[B, H, L, D]`` with ``L`` whole tiles of ``_CAUSAL_FLASH_BLOCK`` and
    at least ``CAUSAL_FLASH_MIN_SEQ``, and head sizes that fit the largest
    the kernel takes."""
    if _cached_backend() != "tpu" or len(q_shape) != 4:
        return False
    l, d = q_shape[2], max(q_shape[3], v_dim)
    return (l % _CAUSAL_FLASH_BLOCK == 0 and l >= CAUSAL_FLASH_MIN_SEQ
            and d <= _FLASH_HEAD_DIMS[-1]
            and jnp.dtype(dtype) in _SUPPORTED_DTYPES)


def causal_attention(q, k, v, sm_scale: float):
    """Causal self-attention of a prompt over its own keys: ``q``, ``k``
    ``[B, H, L, Dk]`` and ``v`` ``[B, H, L, Dv]`` whose sizes may differ
    (latent attention's expanded form: 192 and 128), result ``[B, H, L,
    Dv]``.  Where the flash kernel runs, q, k and v are zero-padded to the
    smallest head size it takes that holds both (256 for 192 / 128): exact
    (a zero channel adds nothing to a score, and a zero value column is cut
    off again), at 1.6 times the quadratic operations; the scores never
    exist in HBM.  Elsewhere the XLA composition."""
    dk, dv = q.shape[-1], v.shape[-1]
    if not causal_flash_supported(q.shape, dv, q.dtype):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
        lq = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((lq, lq), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkv->bhqv",
                          jax.nn.softmax(s, axis=-1).astype(v.dtype), v,
                          preferred_element_type=jnp.float32) \
            .astype(q.dtype)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention as _pallas_flash,
    )

    d = min(c for c in _FLASH_HEAD_DIMS if c >= max(dk, dv))

    def pad(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, d - x.shape[-1]),))

    blk = _CAUSAL_FLASH_BLOCK
    out = _pallas_flash(
        pad(q), pad(k), pad(v), causal=True, sm_scale=float(sm_scale),
        block_sizes=BlockSizes(block_q=blk, block_k_major=blk, block_k=blk,
                               block_b=1))
    return out[..., :dv]


def latent_cache_write(pool, new, phys, off):
    """``pool[phys[b, l], off[b, l]] = new[b, l]`` for a latent pool
    ``[num_blocks, bs, W]`` and a chunk ``[B, L, W]``: ``paged_cache_write``
    without a head axis.  Both leading dimensions are indexed, so the
    scatter's window is the minor-most ``W`` and the donated pool is
    updated where it lies; an index outside the pool is dropped."""
    with jax.named_scope("cache_write"):
        return pool.at[phys, off].set(new.astype(pool.dtype), mode="drop")


def latent_decode_attention(q, latent, table=None, q_pos=None,
                            value_dim: Optional[int] = None,
                            sm_scale: float = 1.0, route=None):
    """A decode step's attention in the ABSORBED form of latent attention.

    ``q`` ``[B, H, Lq, W]``: a head's query through ``W_UK`` (``r``
    values), then its rotary part, then zeros up to ``W``.  ``latent``
    holds what every position keeps, laid out the same way (the latent
    ``c``, the one rotary key every head shares, zeros), either paged
    (``[num_blocks, bs, W]`` behind ``table`` ``[B, max_blocks]``) or by
    slot (``[B, S, W]``, ``table`` None).  ``q_pos`` ``[B, Lq]`` or
    ``[Lq]``: the last position each query sees.  Returns ``sum_i
    softmax_i(q . entry_i * sm_scale) c_i`` ``[B, H, Lq, r]``, ``r =
    value_dim``: still a latent.

    Paged, on a TPU and for a geometry Mosaic takes, the fused kernel
    (``ops.pallas_decode.latent_decode_attention_kernel``): a block is
    streamed once and serves as keys and as values.  Otherwise the XLA
    composition over the gathered entries (float32 scores).  ``route`` as
    in :func:`paged_decode_attention`: ``"pallas"`` runs the kernel (under
    the interpreter off the TPU) or raises why it cannot."""
    from .pallas_decode import (MAX_KERNEL_QUERY_CHUNK,
                                latent_decode_attention_kernel,
                                latent_mosaic_refusal)

    b, h, lq, width = q.shape
    r = width if value_dim is None else int(value_dim)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (b, lq))
    if table is None:
        refusal = ("a latent cache by slot has no fused kernel (the "
                   "kernel walks a block table)")
    elif _cached_backend() == "tpu":
        refusal = latent_mosaic_refusal(h * lq, width, r, latent.shape[1])
    else:
        refusal = None
    with jax.named_scope("latent_attn"):
        if _resolve_route(
                route, q.shape,
                _cached_backend() == "tpu" and refusal is None
                and lq <= MAX_KERNEL_QUERY_CHUNK
                and jnp.dtype(q.dtype) in _SUPPORTED_DTYPES, refusal):
            return latent_decode_attention_kernel(
                q, latent, table, q_pos, r, float(sm_scale),
                interpret=_cached_backend() != "tpu")
        if table is not None:
            latent = latent[jnp.asarray(table, jnp.int32)] \
                .reshape(b, -1, width)
        s = jnp.einsum("bhlw,bsw->bhls", q.astype(latent.dtype), latent,
                       preferred_element_type=jnp.float32)
        seen = jnp.arange(latent.shape[1])[None, None, :] \
            <= q_pos[:, :, None]                              # [B, Lq, S]
        s = jnp.where(seen[:, None], s * sm_scale, -jnp.inf)
        # (a query that sees nothing, q_pos < 0, gives 0 as the kernel
        # does, not the NaN of a softmax over no key)
        p = jnp.where(seen[:, None], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("bhls,bsr->bhlr", p.astype(latent.dtype),
                          latent[..., :r],
                          preferred_element_type=jnp.float32) \
            .astype(q.dtype)


# id(mask) → (weakref(mask), verdict); masks are immutable jax arrays built
# once per model / per trace, so identity caching removes the repeated
# device→host readback.  Weakrefs keep the cache from pinning [L, L] masks
# after their models are freed, and a dead ref also invalidates the entry if
# a new allocation recycles the id (id-only keys are unsound).
_detect_cache: dict = {}
_DETECT_CACHE_MAX = 64


_pad_detect_cache: dict = {}


def detect_padding_additive_mask(mask):
    """[B, 1, 1, Lk] additive padding mask → [B, Lk] bool validity, else
    None.  Catches the standard paddle convention (0 = keep, big-negative =
    pad) so the flash path can use O(L) segment lanes instead of
    broadcasting the bias to [B, H, Lq, Lk] — the exact O(L²·H) HBM
    materialization the kernel exists to avoid.  Only the [B, 1, 1, Lk]
    layout is claimed: a 2-D additive mask means [Lq, Lk] in paddle, which
    is per-query, not key padding.  Concrete masks only; traced masks go
    down the general bias path.  Verdicts are identity-cached like
    ``detect_causal_additive_mask`` — masks are typically built once per
    model, and the readback is a blocking device→host copy."""
    if mask is None or isinstance(mask, jax.core.Tracer):
        return None
    shape = getattr(mask, "shape", None)
    if shape is None or len(shape) != 4 or shape[1] != 1 or shape[2] != 1:
        return None
    import weakref

    key = id(mask)
    hit = _pad_detect_cache.get(key)
    if hit is not None and hit[0]() is mask:
        return hit[1]
    m = np.asarray(mask)[:, 0, 0, :]
    if m.dtype == np.bool_:
        valid = m
    else:
        neg = np.finfo(np.float32).min / 2
        ok = m == 0
        pad = m <= neg
        valid = None if not np.all(ok | pad) else ok  # else: general bias
    try:
        ref = weakref.ref(mask)
    except TypeError:  # pragma: no cover - non-weakrefable array type
        return valid
    if len(_pad_detect_cache) >= _DETECT_CACHE_MAX:
        dead = [k for k, v in _pad_detect_cache.items() if v[0]() is None]
        for k in dead:
            del _pad_detect_cache[k]
        if len(_pad_detect_cache) >= _DETECT_CACHE_MAX:
            _pad_detect_cache.clear()
    _pad_detect_cache[key] = (ref, valid)
    return valid


def detect_causal_additive_mask(mask, seq_len: Optional[int] = None) -> bool:
    """True when ``mask`` is a concrete 2-D additive causal mask (0 on/below
    the diagonal, strictly large-negative above) matching ``seq_len`` — lets
    the kernel's causal fast path replace a materialized mask without
    changing the paddle API.  This also covers jitted callers whose mask is
    built from static shapes (constant-folded to a concrete array inside the
    trace, e.g. TransformerLM._causal_mask); masks that are runtime inputs
    arrive as tracers and safely skip detection."""
    if mask is None or isinstance(mask, jax.core.Tracer):
        return False
    if getattr(mask, "ndim", 0) != 2 or mask.shape[-1] != mask.shape[-2]:
        return False
    l = mask.shape[0]
    if l < 2:  # 1x1 has an empty upper triangle: vacuously "causal"
        return False
    if seq_len is not None and l != seq_len:
        return False  # broadcast-shaped masks keep their loud-error path
    import weakref

    key = id(mask)
    hit = _detect_cache.get(key)
    if hit is not None and hit[0]() is mask:
        return hit[1]
    m = np.asarray(mask)
    allow = np.tril(np.ones((l, l), dtype=bool))  # one L*L bool, no indices
    lower_ok = np.all(np.where(allow, m, 0) == 0)
    upper_ok = np.all(np.where(allow, np.finfo(np.float32).min, m)
                      <= np.finfo(np.float32).min / 2)
    verdict = bool(lower_ok and upper_ok)
    try:
        ref = weakref.ref(mask)
    except TypeError:  # pragma: no cover - non-weakrefable array type
        return verdict
    if len(_detect_cache) >= _DETECT_CACHE_MAX:
        dead = [k for k, v in _detect_cache.items() if v[0]() is None]
        for k in dead:
            del _detect_cache[k]
        if len(_detect_cache) >= _DETECT_CACHE_MAX:
            _detect_cache.clear()
    _detect_cache[key] = (ref, verdict)
    return verdict
