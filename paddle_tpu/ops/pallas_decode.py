"""Fused pallas paged/dense decode-attention kernel (docs/DESIGN.md §5l).

The decode-family steps are cache-bandwidth-bound: one (or a short
chunk of) query positions attend a long KV cache, and the XLA
composition in ``flash_attention.py`` pays for that in HBM round trips
the compiler cannot fuse away ("Operator Fusion in XLA", PAPERS.md):
the paged path's data-dependent table gather MATERIALIZES the gathered
``[B, H, S, D]`` K/V in HBM before attention, and the int8 path's
dequantize up-casts the whole gathered cache to fp32 there too — 4-8x
the bytes the cache actually holds.

This kernel crosses both boundaries by hand.  Per ``(batch row, chunk
of heads, logical block)`` grid step it

- reads the row's block table (a scalar-prefetch operand, so the block
  index feeds the DMA descriptor *before* the body runs) and streams
  that ONE physical K/V block from the pool in HBM into VMEM, for every
  head of the chunk at once (``head_chunk``: all of them where they fit;
  the pool keeps a block's heads contiguous);
- costs what the live K/V costs: a block past the row's last visible
  position (``_last_entry``, from ``q_pos``) is neither fetched (the
  index maps name the last live block again) nor computed;
- dequantizes int8 rows in VMEM — the per-head scales are gathered
  through the SAME table row, so a remapped block always carries its
  own scales;
- applies the lengths/bias masking in-register (``q_pos`` names each
  query's last visible key position; an optional additive bias streams
  block-by-block alongside K/V);
- accumulates attention with an ONLINE softmax across the block axis
  (running max / normalizer / weighted-V in VMEM scratch that persists
  over the sequential grid), so neither the gathered fp32 K/V nor the
  ``[Lq, S]`` score row ever exists in HBM.

``decode_attention_kernel`` is the dense-cache variant on the same
inner loop: the "table" is the identity walk of the ``[B, H, S, D]``
buffer, chunked into sequence tiles.

Shapes are static; query chunks are short (``Lq <= 8`` — single-token
decode and the speculative verify chunk).  ``interpret=True`` runs the
kernel under the pallas interpreter so the SAME body is tier-1-testable
on CPU: numeric identity against the composition is pinned without a
TPU (tests/test_pallas_decode.py), while the routing gates in
``flash_attention.py`` keep compiled-mode engagement TPU-only and
measured-crossover honest.

Compiled under Mosaic the body is held to the TPU's layout rules, and
``mosaic_refusal`` names every geometry it cannot meet so the routing
layer excludes it by reason instead of finding out from the compiler:

- a block's last two dims are whole ``(8, 128)`` tiles or the full
  array dims.  K/V blocks always span the full ``[bs, D]`` dims, which
  is why Mosaic also takes int8 and bf16 blocks below their own 32- and
  16-sublane tiles (compiled and matched on a v5e at ``bs`` 8 and 16);
  the floor on ``block_size`` and the dense tile is 8 sublanes;
- ``q_pos`` is a scalar-prefetch operand in SMEM, which serves scalar
  reads only: the ``[Lq, bs]`` mask threshold is assembled from ``Lq``
  scalar reads, never a vector load;
- int8 scales stream as one ``[hc, bs]`` block (the chunk's heads on
  the sublanes: a multiple of 8 or all of them, lane-major) and a
  head's ``[1, bs]`` row multiplies its ``[Lq, bs]`` score and
  probability rows — ``(q·k)·s == q·(k·s)`` — so no lane vector is
  ever turned into a sublane column;
- the dense tile is bounded (``_DENSE_TILES``): a cache length no tile
  divides is refused, never run as one whole-sequence VMEM block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.errors import InvalidArgumentError

__all__ = ["decode_attention_kernel", "paged_decode_attention_kernel",
           "latent_decode_attention_kernel", "latent_mosaic_refusal",
           "latent_sub_blocks", "MAX_KERNEL_QUERY_CHUNK", "bias_streamable",
           "dense_seq_block", "mosaic_refusal"]

# The longest query chunk the kernel accepts: 1 for autoregressive
# decode, spec_k+1 for a speculative verify chunk.  Longer chunks are
# prefill-shaped work — the flash_attention kernel's territory — and
# the routing layer never sends them here.
MAX_KERNEL_QUERY_CHUNK = 8

# Finite floor for the running max: masked scores are -inf, so with an
# all-masked prefix the running max stays at this floor and
# exp(-inf - floor) == 0 keeps masked positions out of the normalizer
# (a raw -inf running max would turn exp(-inf - -inf) into NaN).
_M_FLOOR = -1e30


# The (sublane, lane) tile every VMEM block is held to.
_SUBLANES = 8
_LANES = 128
_HEAD_DIMS = (64, 128, 256)

# Dense sequence tiles, largest first.  All are whole lane multiples so
# the int8 scale block [H, tile] (sequence on the lane axis) is legal,
# and the largest bounds the VMEM a K/V block can take (512 x 256 x 4 B
# = 512 KiB, double-buffered for K and V = 2 MiB).
_DENSE_TILES = (512, 256, 128)


def dense_seq_block(s: int) -> Optional[int]:
    """Sequence tile for the dense variant: the largest of
    ``_DENSE_TILES`` dividing ``s``; a short cache (``s`` under the
    largest tile) of whole sublanes runs as ONE tile equal to the array
    dim.  None when neither holds — ``mosaic_refusal`` names that case
    and the caller never builds an unbounded whole-sequence block."""
    for cand in _DENSE_TILES:
        if s % cand == 0:
            return cand
    if s < _DENSE_TILES[0] and s % _SUBLANES == 0:
        return s
    return None


def mosaic_refusal(head_dim: int, tile: Optional[int], seq_len: int,
                   has_bias: bool = False) -> Optional[str]:
    """Why Mosaic cannot compile the kernel at this geometry, or None.

    ``tile`` is the K/V block's sequence extent: the paged pool's
    ``block_size``, or ``dense_seq_block``'s answer (None = no tile).
    THE feasibility rule for compiled mode — the ``*_supported`` gates
    and the forced route both read it, so "auto" never picks a refused
    geometry and ``route="pallas"`` raises its reason."""
    if tile is None:
        return ("cache length %d has no sequence tile: none of %s "
                "divides it and it is not a short whole-sublane cache"
                % (seq_len, list(_DENSE_TILES)))
    if head_dim not in _HEAD_DIMS:
        return ("head_dim %d is not one of %s (whole or half 128-lane "
                "tiles the MXU takes)" % (head_dim, list(_HEAD_DIMS)))
    if tile % _SUBLANES != 0:
        return ("K/V block of %d positions is not a multiple of the %d "
                "sublanes a tile holds" % (tile, _SUBLANES))
    if has_bias and tile % _LANES != 0 and tile != seq_len:
        return ("an additive bias streams as [Lq, %d] blocks with the "
                "sequence on the lane axis, which needs a multiple of "
                "%d (pass q_pos/lengths instead)" % (tile, _LANES))
    return None


def bias_streamable(bias_shape, b: int, h: int, lq: int, s: int) -> bool:
    """Whether an additive bias can stream block-wise through the
    kernel: 4-D [B|1, H|1, Lq, S].  THE shape rule — the routing layer
    (flash_attention._bias_kernel_compatible) and the kernel's own
    validation both read it, so they cannot diverge."""
    return (len(bias_shape) == 4 and bias_shape[0] in (1, b)
            and bias_shape[1] in (1, h) and bias_shape[2] == lq
            and bias_shape[3] == s)


def _check_common(q, q_pos, bias, s: int):
    if q.ndim != 4:
        raise InvalidArgumentError(
            "pallas decode kernel needs 4-D [B, H, Lq, D] queries, got "
            "shape %r" % (tuple(q.shape),))
    b, h, lq, _ = q.shape
    if lq > MAX_KERNEL_QUERY_CHUNK:
        raise InvalidArgumentError(
            "pallas decode kernel takes query chunks of at most %d "
            "positions (decode steps and speculative verify chunks), "
            "got Lq=%d — long chunks are prefill work"
            % (MAX_KERNEL_QUERY_CHUNK, lq))
    if q_pos.ndim != 2 or q_pos.shape[0] != b or q_pos.shape[1] != lq:
        raise InvalidArgumentError(
            "q_pos must be [B, Lq] int32 last-visible-key positions "
            "(got %r for q %r)" % (tuple(q_pos.shape), tuple(q.shape)))
    if bias is not None:
        bs_ = getattr(bias, "shape", ())
        if not bias_streamable(bs_, b, h, lq, s):
            raise InvalidArgumentError(
                "kernel bias must be 4-D broadcastable to [B, H, Lq, S]"
                " = %r (got %r); other shapes take the composition path"
                % ((b, h, lq, s), tuple(bs_)))


# VMEM the K and V blocks of one grid step may take: each double-buffered
# by the pipeline in the cache dtype, plus the float32 copy the body makes
# of each.  A quarter of the 16 MiB a v5e kernel is given, so q, the
# output, scores and scratch fit beside them at any geometry.
_KV_VMEM_BUDGET = 4 * 1024 * 1024


def head_chunk(h: int, bs: int, d: int, itemsize: int,
               quant: bool = False) -> int:
    """How many heads of a K/V block one grid step takes: the largest
    divisor of ``h`` whose K and V blocks ``[hc, bs, d]`` fit
    ``_KV_VMEM_BUDGET``.  From shapes alone, so every caller at one
    geometry gets one kernel.

    An int8 cache's scale block is ``[hc, bs]`` with ``hc`` on the
    sublanes: a multiple of 8 or all of ``h`` (the smallest such when
    none fits)."""
    per_head = 2 * bs * d * (2 * itemsize + 4)
    legal = [c for c in range(1, h + 1) if h % c == 0
             and (not quant or c % _SUBLANES == 0 or c == h)]
    fit = [c for c in legal if c * per_head <= _KV_VMEM_BUDGET]
    return max(fit) if fit else min(legal)


def _last_entry(qpos_ref, bi, lq: int, bs: int):
    """The last K/V block any query of batch row ``bi`` may see, from
    ``q_pos`` in SMEM.  0 for a row that sees nothing (``q_pos`` < 0):
    its one block is computed, wholly masked."""
    top = qpos_ref[bi, 0]
    for r in range(1, lq):
        top = jnp.maximum(top, qpos_ref[bi, r])
    # (not negative, so the truncating division is the floor)
    return jax.lax.div(jnp.maximum(top, 0), jnp.int32(bs))


def _make_body(n_scalar: int, lq: int, bs: int, sm_scale: float,
               quant: bool, has_bias: bool, group: int = 1):
    """The shared inner loop over a chunk of ``hc`` heads.  Ref order
    after the ``n_scalar`` scalar-prefetch refs (q_pos always last among
    them): q ``[1, hc, rows, D]``, k, v ``[1, hc, bs, D]``, [k_scale,
    v_scale ``[1, hc, bs]``,] [bias ``[1, hc|1, Lq, bs]``,] out, then
    m/l/acc VMEM scratch with a leading ``hc``.

    ``group`` > 1: the q block holds the ``group`` query heads that
    share each K/V head, ``lq`` rows each (row ``i * lq + l``), and
    ``q_pos`` still has ``lq`` entries a batch row: row ``r`` is held to
    entry ``r % lq``.

    Blocks past the row's last visible one (``_last_entry``) are dead:
    the index maps name the last live block again, so nothing is
    fetched, and the arithmetic is skipped.  A wholly masked block
    leaves m/l/acc as they are (``alpha`` = 1, ``p`` = 0), so the result
    is the same to the bit."""
    rows = group * lq

    def body(*refs):
        qpos_ref = refs[n_scalar - 1]
        q_ref, k_ref, v_ref = refs[n_scalar:n_scalar + 3]
        i = n_scalar + 3
        ks_ref = vs_ref = bias_ref = None
        if quant:
            ks_ref, vs_ref = refs[i:i + 2]
            i += 2
        if has_bias:
            bias_ref = refs[i]
            i += 1
        o_ref, m_ref, l_ref, acc_ref = refs[i:i + 4]

        bi = pl.program_id(0)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _M_FLOOR)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(j <= _last_entry(qpos_ref, bi, lq, bs))
        def _():
            qb = q_ref[0].astype(jnp.float32)           # [hc, rows, D]
            # the HBM read was the cache dtype — the up-cast happens
            # here in VMEM, on one block, never on the gathered cache
            kb = k_ref[0].astype(jnp.float32)           # [hc, bs, D]
            vb = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                qb, kb, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [hc, rows, bs]
            if quant:
                # int8 dequant folded into the score rows: (q·k)·s ==
                # q·(k·s) per key, and a head's [1, bs] scale row is
                # lane-major like its scores
                s = s * ks_ref[0][:, None, :]
            s = s * sm_scale
            if has_bias:
                s = s + bias_ref[0].astype(jnp.float32)
            # mask keys past each query's position (lengths masking,
            # stale table rows, the scratch block's garbage — all arrive
            # as q_pos).  q_pos sits in SMEM: one scalar read per query
            # row, spread over that row's lanes
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0)
            if group > 1:
                row = row % lq
            qp = jnp.full((rows, bs), qpos_ref[bi, 0], jnp.int32)
            for r in range(1, lq):
                qp = jnp.where(row == r, qpos_ref[bi, r], qp)
            pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs),
                                                    1)
            s = jnp.where((pos <= qp)[None], s, -jnp.inf)
            # online softmax: rescale the running sums by
            # exp(m_old - m_new)
            m_prev = m_ref[...]                         # [hc, rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            p = jnp.exp(s - m_new)                      # masked -> 0
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2,
                                                      keepdims=True)
            if quant:
                p = p * vs_ref[0][:, None, :]           # p·(v·s)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, vb, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [hc, rows, D]
            m_ref[...] = m_new

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            l = l_ref[...]
            # a row with no visible key (q_pos < 0 everywhere) emits 0
            # rather than NaN; real decode rows always see position 0
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return body


# batch rows and head chunks are independent; the block axis carries the
# online-softmax state in scratch, so it runs in order
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _scratch(hc: int, rows: int, d: int):
    return [pltpu.VMEM((hc, rows, 1), jnp.float32),   # running max
            pltpu.VMEM((hc, rows, 1), jnp.float32),   # running normalizer
            pltpu.VMEM((hc, rows, d), jnp.float32)]   # weighted-V sum


def _live_block(lq: int, bs: int):
    """``live(b, j, *scalars)`` for the index maps: ``j`` held to the
    row's last live block (``q_pos`` is the last scalar-prefetch ref),
    so a dead step names the block the pipeline already holds."""
    return lambda b, j, *sc: jnp.minimum(j, _last_entry(sc[-1], b, lq, bs))


def _bias_spec(bias_shape, hc: int, lq: int, bs: int, live):
    """The bias block of a head chunk, ``j`` clamped by ``live``."""
    bb, hb = bias_shape[0] > 1, bias_shape[1] > 1
    return pl.BlockSpec(
        (1, hc if hb else 1, lq, bs),
        lambda b, h, j, *sc: (b if bb else 0, h if hb else 0, 0,
                              live(b, j, *sc)))


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "group"))
def _paged_call(q, k_pool, v_pool, table, q_pos, k_scale, v_scale, bias,
                sm_scale, interpret, group=1):
    # grouped K/V heads: ``q`` comes folded, [B, Hkv, group * Lq, D],
    # a K/V head's whole group its block of rows
    b, h, rows, d = q.shape
    lq = rows // group
    _, _, bs, _ = k_pool.shape
    mb = table.shape[1]
    quant = k_scale is not None
    has_bias = bias is not None
    hc = head_chunk(h, bs, d, k_pool.dtype.itemsize, quant)
    live = _live_block(lq, bs)

    def pool_map(bb, hh, j, tbl, qp):
        return (tbl[bb, live(bb, j, tbl, qp)], hh, 0, 0)

    def row_map(bb, hh, j, tbl, qp):
        return (bb, hh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hc, rows, d), row_map),
        pl.BlockSpec((1, hc, bs, d), pool_map),
        pl.BlockSpec((1, hc, bs, d), pool_map),
    ]
    args = [q, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec(
            (1, hc, bs), lambda bb, hh, j, tbl, qp:
            (tbl[bb, live(bb, j, tbl, qp)], hh, 0))] * 2
        args += [k_scale, v_scale]
    if has_bias:
        in_specs.append(_bias_spec(bias.shape, hc, lq, bs, live))
        args.append(bias)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hc, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hc, rows, d), row_map),
        scratch_shapes=_scratch(hc, rows, d))
    return pl.pallas_call(
        _make_body(2, lq, bs, sm_scale, quant, has_bias, group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rows, d), q.dtype),
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(table, q_pos, *args)


def paged_decode_attention_kernel(q, k_pool, v_pool, table, q_pos,
                                  sm_scale: float,
                                  k_scale=None, v_scale=None, bias=None,
                                  interpret: bool = False):
    """Fused paged decode attention: ``q`` [B, H, Lq, D] against a
    block-table pool [num_blocks, H, bs, D], never materializing the
    gathered K/V.

    ``table``: [B, max_blocks] int32 — fed as a scalar-prefetch operand
    so each grid step's DMA streams pool row ``table[b, j]`` directly.
    ``q_pos``: [B, Lq] int32, the last key position each query may
    attend (the causal-prefix / lengths mask in index form; stale table
    rows and the scratch block sit past it and are never read into the
    softmax).  ``k_scale``/``v_scale`` ([num_blocks, H, bs] fp32) mark
    an int8 pool; dequantization happens in VMEM on the streamed block.
    ``bias``: optional additive [B|1, H|1, Lq, S] streamed block-wise.
    """
    nb, h, bs, d = k_pool.shape
    s = table.shape[1] * bs
    _check_common(q, q_pos, bias, s)
    group = 1
    if q.shape[1] != h:
        # a pool of K/V heads under more query heads: the query heads
        # that share a K/V head are one block of the kernel's rows
        if q.shape[1] % h or bias is not None or k_scale is not None:
            raise InvalidArgumentError(
                "grouped K/V heads need query heads (%d) a whole multiple "
                "of the pool's (%d), a float pool and no additive bias"
                % (q.shape[1], h))
        group = q.shape[1] // h
    if table.ndim != 2 or table.shape[0] != q.shape[0]:
        raise InvalidArgumentError(
            "table must be [B, max_blocks] int32 (got %r for q %r)"
            % (tuple(table.shape), tuple(q.shape)))
    if (k_scale is None) != (v_scale is None):
        raise InvalidArgumentError(
            "int8 pools carry BOTH k_scale and v_scale (got one)")
    with jax.named_scope("paged_attn"):
        # one head a head: both reshapes are the identity
        b, _, lq, _ = q.shape
        out = _paged_call(q.reshape(b, h, group * lq, d), k_pool, v_pool,
                          jnp.asarray(table, jnp.int32),
                          jnp.asarray(q_pos, jnp.int32),
                          k_scale, v_scale, bias,
                          float(sm_scale), bool(interpret), group=group)
        return out.reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _dense_call(q, k, v, q_pos, k_scale, v_scale, bias, sm_scale,
                interpret):
    b, h, lq, d = q.shape
    s = k.shape[2]
    bs = dense_seq_block(s)
    mb = s // bs
    quant = k_scale is not None
    has_bias = bias is not None
    hc = head_chunk(h, bs, d, k.dtype.itemsize, quant)
    # a tile past the row's last visible key is dead like a paged block
    live = _live_block(lq, bs)

    def seq_map(bb, hh, j, qp):
        return (bb, hh, live(bb, j, qp), 0)

    def row_map(bb, hh, j, qp):
        return (bb, hh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hc, lq, d), row_map),
        pl.BlockSpec((1, hc, bs, d), seq_map),
        pl.BlockSpec((1, hc, bs, d), seq_map),
    ]
    args = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, hc, bs), lambda bb, hh, j, qp:
                                  (bb, hh, live(bb, j, qp)))] * 2
        args += [k_scale, v_scale]
    if has_bias:
        in_specs.append(_bias_spec(bias.shape, hc, lq, bs, live))
        args.append(bias)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // hc, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hc, lq, d), row_map),
        scratch_shapes=_scratch(hc, lq, d))
    return pl.pallas_call(
        _make_body(1, lq, bs, sm_scale, quant, has_bias),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(q_pos, *args)


def decode_attention_kernel(q, k, v, q_pos, sm_scale: float,
                            k_scale=None, v_scale=None, bias=None,
                            interpret: bool = False):
    """Dense-cache variant of the fused decode kernel: the same online
    softmax inner loop over sequence tiles of a preallocated
    [B, H, S, D] cache (``k_scale``/``v_scale`` [B, H, S] mark the int8
    cache; dequant in VMEM).  ``q_pos``/``bias`` as in the paged
    variant with S = the cache length."""
    if k.ndim != 4:
        raise InvalidArgumentError(
            "dense kernel cache must be [B, H, S, D], got %r"
            % (tuple(k.shape),))
    _check_common(q, q_pos, bias, k.shape[2])
    if (k_scale is None) != (v_scale is None):
        raise InvalidArgumentError(
            "int8 caches carry BOTH k_scale and v_scale (got one)")
    if dense_seq_block(k.shape[2]) is None:
        raise InvalidArgumentError(
            "dense decode kernel: %s"
            % mosaic_refusal(q.shape[3], None, k.shape[2]))
    return _dense_call(q, k, v, jnp.asarray(q_pos, jnp.int32),
                       k_scale, v_scale, bias,
                       float(sm_scale), bool(interpret))


# ---------------------------------------------------------------------------
# latent attention (docs/DESIGN.md section 5w): every head against ONE latent
# ---------------------------------------------------------------------------

# Table entries one grid step of the latent kernel takes.  A latent block
# is small (128 positions x 640 values of bfloat16 = 164 KB: no head axis),
# so a step of one block would be mostly the grid's own overhead; up to
# eight ride one step, each an operand of its own whose index map reads its
# own table entry, and are scored together (``_LATENT_SCORE_TILE``).
_LATENT_SUB_BLOCKS = 8

# Float32 scores one softmax update of the latent kernel may span: the
# entries of a grid step are scored side by side as ONE ``[rows, entries x
# bs]`` tile while it stays under this (1 MiB).  Up to 256 rows (64 heads x
# a chunk of up to four positions) all eight entries of a step, 1,024
# positions, are one tile; at 512 rows (a verify chunk of eight) four are.
# Measured on the v5e at 64, 128, 256 and 512 rows, every width this allows
# and none it does not: the wider tile was the faster at each (the table is
# in docs/DESIGN.md section 5w).
_LATENT_SCORE_TILE = 256 * 1024


def latent_sub_blocks(max_blocks: int, rows: int, block_size: int):
    """``(sub, tile)``.  ``sub``: the table entries a grid step takes, the
    largest divisor of the table width up to ``_LATENT_SUB_BLOCKS``.
    ``tile``: how many of them are scored together and share one softmax
    update, the largest divisor of ``sub`` whose ``rows x tile x
    block_size`` float32 scores fit ``_LATENT_SCORE_TILE`` (1 where not
    even one entry's do).  From shapes alone."""
    sub = max(c for c in range(1, _LATENT_SUB_BLOCKS + 1)
              if max_blocks % c == 0)
    tile = max(c for c in range(1, sub + 1) if sub % c == 0
               and (c == 1 or rows * c * block_size <= _LATENT_SCORE_TILE))
    return sub, tile


def latent_mosaic_refusal(rows: int, width: int, value_dim: int,
                          block_size: int) -> Optional[str]:
    """Why Mosaic cannot compile the latent kernel at this geometry, or
    None.  ``rows`` is heads x query positions, the sublanes of the score
    tile; ``width`` what a position keeps (latent, rotary key, padding),
    ``value_dim`` the latent alone."""
    if width % _LANES != 0:
        return ("a cache entry of %d values a position is not whole "
                "%d-lane tiles" % (width, _LANES))
    if value_dim % _LANES != 0:
        return ("a latent of %d values is not whole %d-lane tiles: the "
                "kernel reads it as the values without a copy"
                % (value_dim, _LANES))
    if block_size % _SUBLANES != 0:
        return ("a latent block of %d positions is not a multiple of the "
                "%d sublanes a tile holds" % (block_size, _SUBLANES))
    if rows % _SUBLANES != 0:
        return ("%d query rows (heads x positions) are not a multiple of "
                "the %d sublanes a tile holds" % (rows, _SUBLANES))
    return None


def _latent_body(lq: int, bs: int, sub: int, tile: int, r: int,
                 sm_scale: float):
    """One batch row against ``sub`` latent blocks a grid step, ``tile`` of
    them at a time as ONE score tile, on ``_make_body``'s online softmax
    and ``_last_entry``'s dead-entry skipping.  Refs after the two
    scalar-prefetch ones: q ``[1, rows, W]`` (the queries through ``W_UK``,
    then their rotary part, then zeros), ``sub`` blocks ``[1, bs, W]``
    (latent, rotary key, zeros), out ``[1, rows, r]``, then m/l/acc
    scratch.  ``rows`` = heads x ``lq``, row ``h * lq + l``: every head
    reads the SAME block, whole as its keys and its first ``r`` lanes as
    its values, fetched once.

    A group of ``tile`` entries whose first is live is ONE region: the
    ``tile`` score products ``q . block^T``, which do not depend on one
    another, laid side by side as ``[rows, tile x bs]``; one mask, one
    max, one ``exp``, one update of m and l, one rescale of acc; then the
    ``tile`` value products, summed.  An entry of the group past the row's
    last live one holds that last live block again (its index map says so)
    under positions past every ``q_pos``: finite scores, masked, ``p``
    exactly 0.  A group whose first entry is dead costs its guard.

    The products take the pool's own type with a float32 accumulator: 64
    heads against one latent are over a hundred operations a byte, so an
    up-cast to float32 (three passes of the MXU and more) would leave the
    kernel bound by its arithmetic instead of the read."""

    def body(tbl_ref, qpos_ref, q_ref, *refs):
        c_refs = refs[:sub]
        o_ref, m_ref, l_ref, acc_ref = refs[sub:]
        bi = pl.program_id(0)
        j = pl.program_id(1)
        rows = q_ref.shape[1]

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _M_FLOOR)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        last = _last_entry(qpos_ref, bi, lq, bs)
        for g in range(0, sub, tile):
            first = j * sub + g

            @pl.when(first <= last)
            def _(g=g, first=first):
                blocks = [c_refs[g + i][0] for i in range(tile)]  # [bs, W]
                s = jnp.concatenate(
                    [jax.lax.dot_general(
                        q_ref[0], cb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                     for cb in blocks], axis=1)           # [rows, tile * bs]
                s = s * sm_scale
                # a row's last visible position, a column: SMEM serves
                # scalar reads only
                row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % lq
                qp = jnp.full((rows, 1), qpos_ref[bi, 0], jnp.int32)
                for t in range(1, lq):
                    qp = jnp.where(row == t, qpos_ref[bi, t], qp)
                pos = first * bs + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(pos <= qp, s, -jnp.inf)
                m_prev = m_ref[...]                          # [rows, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[...] = alpha * l_ref[...] + jnp.sum(
                    p, axis=1, keepdims=True)
                p = p.astype(blocks[0].dtype)
                acc_ref[...] = acc_ref[...] * alpha + sum(
                    jax.lax.dot_general(
                        p[:, i * bs:(i + 1) * bs], cb[:, :r],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    for i, cb in enumerate(blocks))          # [rows, r]
                m_ref[...] = m_new

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            l = l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return body


@functools.partial(jax.jit,
                   static_argnames=("lq", "r", "sm_scale", "interpret"))
def _latent_call(q, latent, table, q_pos, lq, r, sm_scale, interpret):
    b, rows, width = q.shape
    bs = latent.shape[1]
    mb = table.shape[1]
    sub, tile = latent_sub_blocks(mb, rows, bs)

    def row_map(bb, j, tbl, qp):
        return (bb, 0, 0)

    def pool_map(i):
        # a dead entry names the row's last live block again: nothing
        # is fetched for it
        return lambda bb, j, tbl, qp: (
            tbl[bb, jnp.minimum(j * sub + i,
                                _last_entry(qp, bb, lq, bs))], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb // sub),
        in_specs=[pl.BlockSpec((1, rows, width), row_map)]
        + [pl.BlockSpec((1, bs, width), pool_map(i)) for i in range(sub)],
        out_specs=pl.BlockSpec((1, rows, r), row_map),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, r), jnp.float32)])
    return pl.pallas_call(
        _latent_body(lq, bs, sub, tile, r, sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, r), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, q_pos, q, *([latent] * sub))


def latent_decode_attention_kernel(q, latent, table, q_pos, value_dim: int,
                                   sm_scale: float,
                                   interpret: bool = False):
    """Fused latent decode attention: ``q`` ``[B, H, Lq, W]`` (a head's
    query through ``W_UK``, its rotary part, zeros up to ``W``) against a
    block-table pool ``[num_blocks, bs, W]`` of what every position keeps
    (its latent, its rotary key, zeros) and every head shares.  Score ``q
    . entry`` (width ``W``), value the entry's first ``value_dim`` lanes
    (the latent): the result ``[B, H, Lq, value_dim]`` is still a latent,
    and the caller takes it through ``W_UV``.  ``table``/``q_pos`` as in
    the paged K/V kernel."""
    if q.ndim != 4 or latent.ndim != 3 or latent.shape[2] != q.shape[3] \
            or not 0 < value_dim <= q.shape[3]:
        raise InvalidArgumentError(
            "latent decode kernel needs q [B, H, Lq, W] and a pool "
            "[num_blocks, bs, W] with the latent in its first %d lanes, "
            "got %r and %r" % (value_dim, tuple(q.shape),
                               tuple(latent.shape)))
    b, h, lq, width = q.shape
    if lq > MAX_KERNEL_QUERY_CHUNK:
        raise InvalidArgumentError(
            "latent decode kernel takes query chunks of at most %d "
            "positions, got Lq=%d: a longer chunk takes the composition"
            % (MAX_KERNEL_QUERY_CHUNK, lq))
    if table.ndim != 2 or table.shape[0] != b:
        raise InvalidArgumentError(
            "table must be [B, max_blocks] int32 (got %r for q %r)"
            % (tuple(table.shape), tuple(q.shape)))
    if q_pos.shape != (b, lq):
        raise InvalidArgumentError(
            "q_pos must be [B, Lq] int32 last-visible-key positions "
            "(got %r for q %r)" % (tuple(q_pos.shape), tuple(q.shape)))
    out = _latent_call(
        q.reshape(b, h * lq, width).astype(latent.dtype), latent,
        jnp.asarray(table, jnp.int32), jnp.asarray(q_pos, jnp.int32),
        int(lq), int(value_dim), float(sm_scale), bool(interpret))
    return out.reshape(b, h, lq, value_dim).astype(q.dtype)
