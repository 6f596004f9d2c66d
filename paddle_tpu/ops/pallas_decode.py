"""Fused pallas paged/dense decode-attention kernel (docs/DESIGN.md §5l).

The decode-family steps are cache-bandwidth-bound: one (or a short
chunk of) query positions attend a long KV cache, and the XLA
composition in ``flash_attention.py`` pays for that in HBM round trips
the compiler cannot fuse away ("Operator Fusion in XLA", PAPERS.md):
the paged path's data-dependent table gather MATERIALIZES the gathered
``[B, H, S, D]`` K/V in HBM before attention, and the int8 path's
dequantize up-casts the whole gathered cache to fp32 there too — 4-8x
the bytes the cache actually holds.

This kernel crosses both boundaries by hand.  The PAGED kernel
(``_paged_call``) runs one ``(batch row, chunk of heads)`` a grid step and
WALKS the row's block table inside it:

- the pools stay in HBM (``memory_space=pl.ANY``); the table and ``q_pos``
  are scalar-prefetch operands in SMEM.  A row reaches
  ``max(q_pos) // block_size + 1`` entries (``_live_entries``), the loop
  runs ``ceil(that / tile)`` steps, and the row's work ends there: an
  entry past the row's reach is neither fetched nor computed nor visited,
  and a row that sees nothing (``q_pos`` < 0) runs no step and emits 0;
- a step takes ``tile`` entries at once (``paged_tile_entries``: from
  shapes alone, up to eight).  Entry ``e`` is copied by hand
  (``pltpu.make_async_copy``), ``pool[table[b, e]]`` for every head of the
  chunk at once (``head_chunk``: the pool keeps a block's heads
  contiguous) to its ``block_size`` rows of a ``[hc, tile * block_size,
  D]`` VMEM buffer.  Two buffers: tile ``t + 1`` is in flight while tile
  ``t`` is computed, and the first tile of the NEXT grid step is started
  before the last tile of this one is computed, so a row does not wait
  for a copy it has just asked for;
- a tile is ONE score tile: one product ``q . K^T`` over ``[rows, tile *
  block_size]``, one mask, one max, one ``exp``, one update of the
  ONLINE softmax's running max / normalizer / weighted-V (VMEM scratch),
  one product with V.  Neither the gathered fp32 K/V nor the ``[Lq, S]``
  score row ever exists in HBM;
- int8 rows are dequantized in VMEM, the HBM read stays int8.  Their
  per-head scales are small (1/32 of the pool's bytes at a head of 128)
  with the positions on the lane axis, ``[num_blocks, H, block_size]``,
  and Mosaic slices no HBM
  array whose minor dimension is not whole 128-lane tiles: so
  ``_paged_call`` gathers the scales of the row's entries through the
  table (XLA, outside the kernel; V's set to 0 past the row's reach) and
  lays them out by the walk's steps, ``[B, steps, H, lanes]`` with a
  step's ``tile * block_size`` positions in a row of whole lanes
  (``_by_steps``).  A step's scales are then ONE hand copy beside its K
  and V entries, at any block size;
- the lengths/bias masking is applied in-register (``q_pos`` names each
  query's last visible key position; an optional additive bias is laid
  out by the walk's steps as the scales are, and copied a step at a
  time).

``paged_kv_write_kernel`` (``_kv_write_call``) is the walk's counterpart
on the way IN: a decode step's new K and V rows go into their pools by one
kernel a layer, the pools left in HBM and aliased input to output, each
row read and written back with the tile of rows it lies in
(``write_group``; ``_kv_write_body`` has why, docs/DESIGN.md §5l "The
write").  The XLA scatter it replaces costs one index row a (slot, head)
whatever the row's bytes.

``decode_attention_kernel`` is the dense-cache variant: the "table" is the
identity walk of the ``[B, H, S, D]`` buffer, chunked into sequence tiles,
one tile a step of a ``(row, head chunk, tile)`` grid under the pipeline's
own copies (``_make_body``); a tile past the row's last visible position
is dead there: its index map names the last live tile again, so nothing
is fetched, and its arithmetic is skipped.

Shapes are static; query chunks are short (``Lq <= 8`` — single-token
decode and the speculative verify chunk).  ``interpret=True`` runs the
kernel under the pallas interpreter so the SAME body is tier-1-testable
on CPU: numeric identity against the composition is pinned without a
TPU (tests/test_pallas_decode.py), while the routing gates in
``flash_attention.py`` keep compiled-mode engagement TPU-only and
measured-crossover honest.

Compiled under Mosaic the bodies are held to the TPU's layout rules, and
``mosaic_refusal`` / ``paged_mosaic_refusal`` name every geometry they
cannot meet so the routing layer excludes it by reason instead of finding
out from the compiler:

- a block's last two dims are whole ``(8, 128)`` tiles or the full
  array dims; the floor on ``block_size`` and the dense tile is 8
  sublanes;
- the paged walk copies out of HBM by hand, and Mosaic takes no slice of
  an HBM array whose minor dimension is not whole 128-lane tiles: a
  ``head_dim`` of 64 is refused there (the one-entry-a-step kernel this
  walk replaced took it through the pipeline's copies: PERF.md section 6,
  PR 43, has what the composition costs there).  An int8 pool's scales
  and a bias reach the kernel in rows of whole lanes (above) and add no
  rule.  An entry lands at row ``e * block_size`` of a tile, which has to
  be a whole sublane tile of the pool's type (8 float32, 16 bfloat16, 32
  int8 rows): where it is not, a tile is one entry;
- ``q_pos`` is a scalar-prefetch operand in SMEM, which serves scalar
  reads only: the mask threshold is assembled from ``Lq`` scalar reads,
  never a vector load;
- int8 scales are ``[hc, positions]`` (the chunk's heads on the sublanes: a
  multiple of 8 or all of them, lane-major) and a head's row multiplies
  its score and probability rows: ``(q.k).s == q.(k.s)``, so no lane
  vector is ever turned into a sublane column;
- the dense tile is bounded (``_DENSE_TILES``): a cache length no tile
  divides is refused, never run as one whole-sequence VMEM block.
"""
from __future__ import annotations

import contextlib
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
           "dense_seq_block", "mosaic_refusal", "paged_mosaic_refusal",
           "paged_tile_entries", "paged_kv_write_kernel",
           "kv_write_mosaic_refusal", "write_group"]

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


def paged_mosaic_refusal(head_dim: int, block_size: int) -> Optional[str]:
    """Why Mosaic cannot compile the PAGED kernel at this geometry, or
    None: ``mosaic_refusal``'s rules for a K/V block, and the walk's own.
    The pools stay in HBM and the kernel copies a table entry by hand; a
    copy out of an HBM array is whole 128-lane tiles in its minor
    dimension (Mosaic refuses the slice of a narrower one: compiled for
    the v5e, ``tests/test_tpu_compile.py``), and the minor dimension of a
    K/V pool is ``head_dim``.  An int8 pool's scales and a bias do not
    add a rule: ``_paged_call`` lays them out by the walk's steps in rows
    of whole lanes (``_by_steps``) whatever the block."""
    why = mosaic_refusal(head_dim, block_size, block_size)
    if why is not None:
        return why
    if head_dim % _LANES != 0:
        return ("head_dim %d is not whole %d-lane tiles: the pools stay in "
                "HBM and an entry is copied by hand, which a narrower "
                "minor dimension does not allow" % (head_dim, _LANES))
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


def _kv_vmem_bytes(bs: int, d: int, itemsize: int) -> int:
    """VMEM one head of a K and a V block of ``bs`` positions takes: two
    buffers each in the cache dtype, plus the float32 copy the body
    makes of each."""
    return 2 * bs * d * (2 * itemsize + 4)


def head_chunk(h: int, bs: int, d: int, itemsize: int,
               quant: bool = False) -> int:
    """How many heads of a K/V block one grid step takes: the largest
    divisor of ``h`` whose K and V blocks ``[hc, bs, d]`` fit
    ``_KV_VMEM_BUDGET``.  From shapes alone, so every caller at one
    geometry gets one kernel.

    An int8 cache's scale block is ``[hc, bs]`` with ``hc`` on the
    sublanes: a multiple of 8 or all of ``h`` (the smallest such when
    none fits)."""
    per_head = _kv_vmem_bytes(bs, d, itemsize)
    legal = [c for c in range(1, h + 1) if h % c == 0
             and (not quant or c % _SUBLANES == 0 or c == h)]
    fit = [c for c in legal if c * per_head <= _KV_VMEM_BUDGET]
    return max(fit) if fit else min(legal)


def _top_position(qpos_ref, bi, lq: int):
    """The last position any query of batch row ``bi`` may see, from
    ``q_pos`` in SMEM (scalar reads); negative for a row that sees
    nothing."""
    top = qpos_ref[bi, 0]
    for r in range(1, lq):
        top = jnp.maximum(top, qpos_ref[bi, r])
    return top


def _last_entry(qpos_ref, bi, lq: int, bs: int):
    """The last K/V block any query of batch row ``bi`` may see.  0 for a
    row that sees nothing (``q_pos`` < 0): its one block is computed,
    wholly masked."""
    # (not negative, so the truncating division is the floor)
    return jax.lax.div(jnp.maximum(_top_position(qpos_ref, bi, lq), 0),
                       jnp.int32(bs))


def _softmax_update(s, vb, v_scale, m_ref, l_ref, acc_ref):
    """One step of the online softmax: masked scores ``s`` ``[hc, rows,
    keys]`` (-inf where masked) and values ``vb`` ``[hc, keys, D]`` into
    the running max / normalizer / weighted-V, the old sums rescaled by
    ``exp(m_old - m_new)``.  ``v_scale`` ``[hc, keys]``: an int8 pool's V
    scales, folded into the probability rows (``p.(v.s)``)."""
    m_prev = m_ref[...]                                 # [hc, rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)                              # masked -> 0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
    if v_scale is not None:
        p = p * v_scale[:, None, :]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, vb, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)             # [hc, rows, D]
    m_ref[...] = m_new


def _make_body(n_scalar: int, lq: int, bs: int, sm_scale: float,
               quant: bool, has_bias: bool):
    """The dense kernel's inner loop over a chunk of ``hc`` heads, one
    sequence tile a grid step.  Ref order after the ``n_scalar``
    scalar-prefetch refs (q_pos always last among them): q ``[1, hc, Lq,
    D]``, k, v ``[1, hc, bs, D]``, [k_scale, v_scale ``[1, hc, bs]``,]
    [bias ``[1, hc|1, Lq, bs]``,] out, then m/l/acc VMEM scratch with a
    leading ``hc``.

    Tiles past the row's last visible one (``_last_entry``) are dead:
    the index maps name the last live tile again, so nothing is
    fetched, and the arithmetic is skipped.  A wholly masked tile
    leaves m/l/acc as they are (``alpha`` = 1, ``p`` = 0), so the result
    is the same to the bit."""
    rows = lq

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
            qp = jnp.full((rows, bs), qpos_ref[bi, 0], jnp.int32)
            for r in range(1, lq):
                qp = jnp.where(row == r, qpos_ref[bi, r], qp)
            pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs),
                                                    1)
            s = jnp.where((pos <= qp)[None], s, -jnp.inf)
            _softmax_update(s, vb, vs_ref[0] if quant else None,
                            m_ref, l_ref, acc_ref)

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


# Table entries one step of the paged walk takes at most.  The entries
# of a tile are copied one by one (each its own descriptor: the table
# scatters them over the pool), so the width also bounds the copies a
# step issues and waits for.
_PAGED_TILE_ENTRIES = 8

# Float32 scores one softmax update of the paged walk may span, as
# ``_LATENT_SCORE_TILE``: 256 Ki values, 1 MiB.
_PAGED_SCORE_TILE = 256 * 1024


def paged_tile_entries(hc: int, rows: int, bs: int, d: int, itemsize: int,
                       max_blocks: int) -> int:
    """How many table entries one step of the paged walk takes, scored
    as ONE ``[hc, rows, tile * bs]`` tile: the largest count, up to
    ``_PAGED_TILE_ENTRIES`` and the table's width, whose K and V tiles
    (two buffers each in the cache dtype plus the float32 copy the body
    makes: ``head_chunk``'s arithmetic) fit ``_KV_VMEM_BUDGET`` and whose
    float32 scores fit ``_PAGED_SCORE_TILE``.  From shapes alone.

    An entry lands at row ``i * bs`` of the tile, so ``bs`` has to be
    whole sublane tiles of the cache dtype (8 float32, 16 bfloat16, 32
    int8 rows).  Where it is not, an entry is a tile of its own: the
    buffer is the entry."""
    if bs % (4 * _SUBLANES // itemsize):
        return 1
    kv = hc * _kv_vmem_bytes(bs, d, itemsize)
    padded = -(-rows // _SUBLANES) * _SUBLANES
    return max(1, min(_PAGED_TILE_ENTRIES, max_blocks,
                      _KV_VMEM_BUDGET // kv,
                      _PAGED_SCORE_TILE // (hc * padded * bs)))


def _live_entries(qpos_ref, bi, lq: int, bs: int, mb: int):
    """How many table entries batch row ``bi`` reaches, from ``q_pos`` in
    SMEM: 0 for a row that sees nothing (``q_pos`` < 0 everywhere)."""
    top = _top_position(qpos_ref, bi, lq)
    reach = jnp.minimum(jax.lax.div(jnp.maximum(top, 0), jnp.int32(bs)) + 1,
                        jnp.int32(mb))
    return jnp.where(top < 0, 0, reach)


def _window_entries(qpos_ref, bi, bs: int, mb: int, window: int):
    """``(first, count)``: the logical table entries a row of ONE query
    reads against a WINDOW entry, whose keys are the ``window`` positions
    that end at the query's own: from the entry that holds position ``top
    - window + 1`` (0 while the context is shorter than the window) to the
    one that holds ``top``.  At most ``window / bs + 1`` of them, a ring's
    length, whatever the context; 0 for a row that sees nothing."""
    top = qpos_ref[bi, 0]
    first = jax.lax.div(jnp.maximum(top - (window - 1), 0), jnp.int32(bs))
    last = jax.lax.div(jnp.maximum(top, 0), jnp.int32(bs))
    return first, jnp.where(top < 0, 0,
                            jnp.minimum(last - first + 1, jnp.int32(mb)))


def _by_steps(x, steps: int, width: int):
    """``x`` ``[B, .., S]``, positions on the lane axis (an int8 pool's
    scales gathered through the table, a bias), as ``[B, steps, ..,
    lanes]``: the ``width`` positions one step of the walk scores, in a
    row of whole 128-lane tiles.  A step's share is then ONE hand copy
    whatever the block's size (Mosaic slices no narrower HBM array), and
    lands where the step's scores lie."""
    lanes = -(-width // _LANES) * _LANES
    lead = [(0, 0)] * (x.ndim - 1)
    x = jnp.pad(x, lead + [(0, steps * width - x.shape[-1])])
    x = x.reshape(x.shape[:-1] + (steps, width))
    x = jnp.pad(x, lead + [(0, 0), (0, lanes - width)])
    return jnp.moveaxis(x, -2, 1)


def _paged_body(hc: int, mb: int, lq: int, bs: int, tile: int,
                sm_scale: float, quant: bool, bias_dims, group: int,
                planed: bool = False, window: Optional[int] = None):
    """One ``(batch row, head chunk)`` a grid step: a loop over the row's
    LIVE table entries, ``tile`` of them a step.

    Refs after the two scalar-prefetch ones (``table``, ``q_pos``): q
    ``[1, hc, rows, D]`` (VMEM, the pipeline's); the K and V pools, [the
    int8 pool's scales of the row's entries ``[B, steps, H, lanes]``,
    twice,] [the bias ``[B|1, steps, H|1, Lq, lanes]``,] (``_by_steps``)
    all left in HBM; out; then scratch: two K and two V buffers ``[2, hc,
    tile * bs, D]`` in the pool's type, [scales ``[2, hc, lanes]``,]
    [bias ``[2, hc|1, Lq, lanes]``,] DMA semaphores ``[2, streams]``, two
    int32 of SMEM that outlive a grid step (which buffer the next tile
    lands in; whether this step's first tile was started by the step
    before), and m / l / acc as ``_make_body`` has them.

    Entry ``e`` of the row is copied by hand, ``pool[table[b, e], chunk]``
    to rows ``(e % tile) * bs ...`` of a buffer; entries of the last tile
    past the row's reach are not copied and sit under positions past every
    ``q_pos``: masked, ``p`` exactly 0 (the V buffers start as zeros, and
    hold live entries' values ever after: 0 x finite).  The scales and the
    bias of a tile are one copy each.  Tile ``t + 1`` is
    in flight while tile ``t`` is computed, and the first tile of the NEXT
    grid step is started before the last tile of this one is computed, so
    only the first live row of a call, and a live row behind an empty one,
    wait for a copy they have just asked for.  A row that sees nothing
    starts nothing, runs no tile and emits zeros.

    ``planed``: a third scalar-prefetch ref follows ``q_pos``, one int32:
    the first head of the PLANE of the pools that this call attends (the
    pools hold several planes of the queries' K/V heads side by side on
    their head axis).  A copy takes ``pool[table[b, e], base + chunk]``:
    the same contiguous bytes of an entry as an unplaned pool's, further
    along.

    ``window`` (one query a row, a float pool, no bias): the table is a
    RING of ``mb`` entries, position ``p`` lives at entry ``(p // bs) %
    mb``, and the query sees the ``window`` positions that end at its own.
    The walk then has a FIRST live entry beside the last
    (``_window_entries``): tile ``t`` takes logical entries ``first + t *
    tile ...``, looked up at their ring places, and the positions at or
    before ``q_pos - window``, which lie inside the first entry, are masked
    like those past ``q_pos``.  With ``window`` None none of this is
    traced: the body is what it was."""
    rows = group * lq
    width = tile * bs
    has_bias = bias_dims is not None

    def body(tbl_ref, qpos_ref, *refs):
        base = refs[0][0] if planed else 0
        q_ref, k_hbm, v_hbm, *refs = refs[int(planed):]
        # the streams that follow the walk's steps, then out, then a
        # buffer a stream: K, V, [K scales, V scales,] [bias]
        n_side = 2 * quant + has_bias
        side_hbm = refs[:n_side]
        o_ref, k_buf, v_buf = refs[n_side:n_side + 3]
        side_buf = refs[n_side + 3:2 * n_side + 3]
        sems, state, m_ref, l_ref, acc_ref = refs[2 * n_side + 3:]

        bi, hh = pl.program_id(0), pl.program_id(1)
        b, nh = pl.num_programs(0), pl.num_programs(1)

        def each_copy(row, chunk, n, t, slot, act, start=0):
            """``act`` on every copy of tile ``t`` of ``(row, chunk)``,
            a row of ``n`` live entries, into buffer ``slot``: one K and
            one V descriptor a live entry [, the tile's scales and
            bias].  ``start``: the row's first live entry (a window's
            walk; the table is then a ring)."""
            heads = pl.ds(chunk * hc, hc)
            pool_heads = pl.ds(base + chunk * hc, hc) if planed else heads
            for s, (src, dst) in enumerate(zip(side_hbm, side_buf)):
                if has_bias and s == n_side - 1:
                    # the bias may be one row or one head for all
                    src = src.at[row if bias_dims[0] > 1 else 0, t,
                                 heads if bias_dims[1] > 1 else pl.ds(0, 1)]
                else:
                    src = src.at[row, t, heads]
                act(pltpu.make_async_copy(src, dst.at[slot],
                                          sems.at[slot, 2 + s]))
            for j in range(tile):
                entry = t * tile + j
                at = pl.ds(j * bs, bs)

                @pl.when(entry < n)
                def _(entry=entry, at=at):
                    blk = tbl_ref[row, entry] if window is None else \
                        tbl_ref[row, jax.lax.rem(start + entry,
                                                 jnp.int32(mb))]
                    for s, (src, dst) in enumerate(((k_hbm, k_buf),
                                                    (v_hbm, v_buf))):
                        act(pltpu.make_async_copy(
                            src.at[blk, pool_heads], dst.at[slot, :, at, :],
                            sems.at[slot, s]))

        @pl.when(jnp.logical_and(bi == 0, hh == 0))
        def _():
            state[0] = 0
            state[1] = 0
            v_buf[...] = jnp.zeros_like(v_buf)

        m_ref[...] = jnp.full_like(m_ref, _M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        # a row's first live entry: 0 without a window
        first_live = first_next = 0
        if window is None:
            n_live = _live_entries(qpos_ref, bi, lq, bs, mb)
        else:
            first_live, n_live = _window_entries(qpos_ref, bi, bs, mb,
                                                 window)
        n_tiles = jax.lax.div(n_live + (tile - 1), jnp.int32(tile))
        first = state[0]
        # the grid step after this one, and whether it has a tile
        wraps = hh == nh - 1
        nrow = jnp.minimum(jnp.where(wraps, bi + 1, bi), b - 1)
        nchunk = jnp.where(wraps, 0, hh + 1)
        if window is None:
            n_next = _live_entries(qpos_ref, nrow, lq, bs, mb)
        else:
            first_next, n_next = _window_entries(qpos_ref, nrow, bs, mb,
                                                 window)
        follows = jnp.logical_and(
            jnp.logical_not(jnp.logical_and(wraps, bi == b - 1)),
            n_next > 0)

        @pl.when(jnp.logical_and(n_tiles > 0, state[1] == 0))
        def _():
            each_copy(bi, hh, n_live, 0, first, lambda c: c.start(),
                      first_live)

        qb = q_ref[0].astype(jnp.float32)               # [hc, rows, D]
        # a row's last visible position, a column: SMEM serves scalar
        # reads only
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        if group > 1:
            row = row % lq
        qp = jnp.full((rows, 1), qpos_ref[bi, 0], jnp.int32)
        for r in range(1, lq):
            qp = jnp.where(row == r, qpos_ref[bi, r], qp)

        def tile_step(t, carry):
            slot = jnp.bitwise_and(first + t, 1)
            more = t + 1 < n_tiles

            @pl.when(jnp.logical_or(more, follows))
            def _():
                each_copy(jnp.where(more, bi, nrow),
                          jnp.where(more, hh, nchunk),
                          jnp.where(more, n_live, n_next),
                          jnp.where(more, t + 1, 0), 1 - slot,
                          lambda c: c.start(),
                          0 if window is None
                          else jnp.where(more, first_live, first_next))

            each_copy(bi, hh, n_live, t, slot, lambda c: c.wait(),
                      first_live)
            # the HBM read was the cache dtype: the up-cast happens here
            # in VMEM, on one tile, never on the gathered cache
            kb = k_buf[slot].astype(jnp.float32)        # [hc, width, D]
            vb = v_buf[slot].astype(jnp.float32)
            # (a buffer's lanes past the tile's width are padding)
            side = [buf[slot][..., :width] for buf in side_buf]
            s = jax.lax.dot_general(
                qb, kb, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [hc, rows, width]
            if quant:
                # int8 dequant folded into the score rows: (q.k).s ==
                # q.(k.s) per key, and a head's scale row is lane-major
                # like its scores
                s = s * side[0][:, None, :]
            s = s * sm_scale
            if has_bias:
                s = s + side[-1].astype(jnp.float32)
            pos = t * width + jax.lax.broadcasted_iota(
                jnp.int32, (rows, width), 1)
            if window is None:
                seen = pos <= qp
            else:
                # the tile's positions start at the row's first live
                # entry; the band's lower edge lies inside that entry
                pos = pos + first_live * bs
                seen = jnp.logical_and(pos <= qp, pos > qp - window)
            s = jnp.where(seen[None], s, -jnp.inf)
            _softmax_update(s, vb, side[1] if quant else None,
                            m_ref, l_ref, acc_ref)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile_step, 0)

        @pl.when(n_tiles > 0)
        def _():
            state[0] = jnp.bitwise_and(first + n_tiles, 1)
            state[1] = follows.astype(jnp.int32)

        l = l_ref[...]
        # a row with no visible key emits 0 rather than NaN
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return body


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "group",
                                    "window"))
def _paged_call(q, k_pool, v_pool, table, q_pos, k_scale, v_scale, bias,
                sm_scale, interpret, group=1, head_base=None, window=None):
    # grouped K/V heads: ``q`` comes folded, [B, Hkv, group * Lq, D],
    # a K/V head's whole group its block of rows.  ``head_base`` (int32
    # [1], traced): the pools hold planes of Hkv heads and this call
    # attends the one that starts there.  ``window`` (static): the table
    # is a ring and a query sees the ``window`` positions that end at its
    # own (``_paged_body``); None traces the call as it was
    b, h, rows, d = q.shape
    lq = rows // group
    _, _, bs, _ = k_pool.shape
    mb = table.shape[1]
    quant = k_scale is not None
    has_bias = bias is not None
    hc = head_chunk(h, bs, d, k_pool.dtype.itemsize, quant)
    tile = paged_tile_entries(hc, rows, bs, d, k_pool.dtype.itemsize, mb)
    steps = -(-mb // tile)
    lanes = -(-tile * bs // _LANES) * _LANES

    def row_map(bb, hh, *scalars):
        return (bb, hh, 0, 0)

    planed = head_base is not None
    scalars = (table, q_pos) + ((head_base,) if planed else ())
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    args = [q, k_pool, v_pool]
    buffers = [pltpu.VMEM((2, hc, tile * bs, d), k_pool.dtype)] * 2
    if quant:
        # the scales of the row's own entries, [B, H, S] as the
        # composition gathers them; V's are 0 past the row's reach, where
        # a stale entry's may be anything and p is exactly 0
        seen = jnp.arange(mb * bs) <= jnp.max(q_pos, axis=1, keepdims=True)
        for scale, keep in ((k_scale, None), (v_scale, seen[:, None])):
            scale = scale[table].transpose(0, 2, 1, 3).reshape(b, h, -1)
            if keep is not None:
                scale = jnp.where(keep, scale, 0.0)
            args.append(_by_steps(scale, steps, tile * bs))
        buffers += [pltpu.VMEM((2, hc, lanes), k_scale.dtype)] * 2
    if has_bias:
        args.append(_by_steps(bias, steps, tile * bs))
        buffers.append(pltpu.VMEM(
            (2, hc if bias.shape[1] > 1 else 1, lq, lanes), bias.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, h // hc),
        in_specs=[pl.BlockSpec((1, hc, rows, d), row_map)]
        + [in_hbm] * (len(args) - 1),
        out_specs=pl.BlockSpec((1, hc, rows, d), row_map),
        scratch_shapes=buffers
        + [pltpu.SemaphoreType.DMA((2, len(args) - 1)),
           pltpu.SMEM((2,), jnp.int32)] + _scratch(hc, rows, d))
    return pl.pallas_call(
        _paged_body(hc, mb, lq, bs, tile, sm_scale, quant,
                    bias.shape if has_bias else None, group, planed,
                    window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rows, d), q.dtype),
        # a copy in flight and the buffer it lands in pass from one grid
        # step to the next: the steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*scalars, *args)


def paged_decode_attention_kernel(q, k_pool, v_pool, table, q_pos,
                                  sm_scale: float,
                                  k_scale=None, v_scale=None, bias=None,
                                  interpret: bool = False, head_base=None,
                                  plane_heads: Optional[int] = None,
                                  window: Optional[int] = None):
    """Fused paged decode attention: ``q`` [B, H, Lq, D] against a
    block-table pool [num_blocks, H, bs, D], never materializing the
    gathered K/V.

    ``table``: [B, max_blocks] int32, a scalar-prefetch operand: the
    kernel walks a row's LIVE entries, several a step, and copies pool
    row ``table[b, e]`` by hand from the pools, which stay in HBM.
    ``q_pos``: [B, Lq] int32, the last key position each query may
    attend (the causal-prefix / lengths mask in index form; stale table
    rows and the scratch block sit past it and are neither fetched nor
    computed; a row with every ``q_pos`` < 0 emits zeros).
    ``k_scale``/``v_scale`` ([num_blocks, H, bs] fp32) mark an int8
    pool; dequantization happens in VMEM on the copied tile, the scales
    gathered through the table and laid out by the walk's steps first
    (``_by_steps``: any ``bs`` of whole sublanes).  ``bias``: optional
    additive [B|1, H|1, Lq, S], laid out the same way.  Compiled, the
    pools' ``head_dim`` is whole 128-lane tiles
    (``paged_mosaic_refusal``).

    ``head_base`` (an int32 scalar, traced) with ``plane_heads``: the
    pools are ``[num_blocks, planes * plane_heads, bs, D]``, several
    planes of K/V heads an entry, and the queries attend the plane whose
    first head is ``head_base``; one more scalar-prefetch operand, the
    walk and its copies otherwise the same.  A float pool, no bias.

    ``window``: the entry is a WINDOW entry (``jit.cache.WindowLayout``):
    ``table`` ``[B, ring]`` is a ring, position ``p`` lives at entry ``(p
    // bs) % ring``, and a query sees positions ``q_pos - window < j <=
    q_pos``.  The walk runs from the entry of ``q_pos - window + 1`` to the
    entry of ``q_pos``, at most ``window / bs + 1`` entries whatever the
    context.  One query a row, a float pool, no bias, no planes."""
    nb, h, bs, d = k_pool.shape
    planed = head_base is not None
    if window is not None and (q.shape[2] != 1 or planed or bias is not None
                               or k_scale is not None):
        raise InvalidArgumentError(
            "the windowed walk takes ONE query a row against a float pool "
            "of one plane, no additive bias (got a chunk of %d%s): a "
            "longer chunk against a ring of blocks takes the composition"
            % (q.shape[2], ", planes" if planed else ""))
    if planed:
        if k_scale is not None or bias is not None:
            raise InvalidArgumentError(
                "a pool of several K/V planes is a float pool attended "
                "without an additive bias")
        h = int(plane_heads)
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
    # a window entry's call is a child of the scope: ``paged_attn/window``
    with jax.named_scope("paged_attn"), (
            contextlib.nullcontext() if window is None
            else jax.named_scope("window")):
        # one head a head: both reshapes are the identity
        b, _, lq, _ = q.shape
        out = _paged_call(q.reshape(b, h, group * lq, d), k_pool, v_pool,
                          jnp.asarray(table, jnp.int32),
                          jnp.asarray(q_pos, jnp.int32),
                          k_scale, v_scale, bias,
                          float(sm_scale), bool(interpret), group=group,
                          **({"head_base": jnp.reshape(jnp.asarray(
                              head_base, jnp.int32), (1,))}
                             if planed else {}),
                          window=None if window is None else int(window))
        return out.reshape(q.shape)


def _tile_rows(itemsize: int) -> int:
    """The rows of a sublane tile of values of ``itemsize`` bytes: 8
    float32, 16 bfloat16, 32 int8."""
    return 4 * _SUBLANES // itemsize


def write_group(block_size: int, itemsize: int) -> int:
    """The rows of a block that the K/V write reads and writes back around
    ONE new row: a sublane tile of the pool's type (8 float32 rows, 16
    bfloat16: a pool lies tiled so in HBM, and Mosaic copies no slice of
    fewer rows).  A block that no such tile divides is one group (the
    interpreter's; ``kv_write_mosaic_refusal`` names it on a TPU)."""
    g = _tile_rows(itemsize)
    return block_size if block_size % g else g


def kv_write_mosaic_refusal(head_dim: int, block_size: int,
                            itemsize: int) -> Optional[str]:
    """Why Mosaic cannot compile the K/V write kernel at this geometry, or
    None.  The pools stay in HBM and a row's group is copied by hand: the
    pool's minor dimension is whole 128-lane tiles (as
    ``paged_mosaic_refusal``) and a block is whole sublane tiles of the
    pool's type (``write_group``)."""
    if head_dim % _LANES != 0:
        return ("head_dim %d is not whole %d-lane tiles: the pools stay in "
                "HBM and a row's group is copied by hand, which a narrower "
                "minor dimension does not allow" % (head_dim, _LANES))
    g = _tile_rows(itemsize)
    if block_size % g != 0:
        return ("K/V block of %d positions is not a multiple of the %d rows "
                "a tile of %d-byte values holds: a new row is written with "
                "its tile's other rows" % (block_size, g, itemsize))
    return None


def _kv_write_body(n: int, lq: int, h: int, g: int, nb: int, planed: bool):
    """``n`` slots a grid step, ``lq`` positions a slot: every new row
    into its pools where they lie, by a read-modify-write of the row's
    GROUP (``write_group``; a pool's tile in HBM is ``g`` rows, and no
    copy is narrower).

    Refs: scalar prefetch ``phys`` and ``off`` ``[B * lq]`` [, the
    plane's first head ``[1]``]; the step's new K and V rows ``[n * lq, h,
    D]`` (VMEM, the pipeline's); the K and V pools twice, in and out, ONE
    buffer each (``input_output_aliases``), left in HBM; scratch: a group
    a new row and pool ``[n * lq, h, g, D]``, four DMA semaphores (K and V,
    in and out).

    Row ``i``'s group is ``pool[phys[i], base:base + h, off[i] // g * g
    ...]``.  The positions of ONE slot may share a group (a chunk of
    ``lq`` > 1) and must not lose each other's rows, so a group is read
    and written back by the first of the slot's rows that names it (its
    LEADER, ``lead``) and the rows that follow are put into the leader's
    buffer.  Two slots share no block but the scratch block, where what
    lands is not defined.  Every read is started before the first is
    waited for, then the new rows are put in (a select on the row's
    index in its group), then every write is started before the first is
    waited for.  A row whose block lies outside ``[0, nb)`` is DROPPED: no
    copy is made for it."""
    def body(phys_ref, off_ref, *refs):
        base = refs[0][0] if planed else 0
        k_new, v_new, _, _, k_hbm, v_hbm, k_buf, v_buf, sems = \
            refs[int(planed):]
        first = pl.program_id(0) * (n * lq)

        def lead(slot, l, named):
            """The buffer (this step's) that row ``l`` of ``slot`` is
            put into: its own, or that of the slot's first row in the
            same group.  ``named`` holds the (block, group) of the
            slot's rows before ``l`` and takes row ``l``'s: each is read
            ONCE, not once a pair of rows (a chunk of 8 compares 28 pairs
            a slot, and every ``//`` is lowered through a traced
            ``sign``: an offset is not negative, so the truncating
            division is the floor).  A chunk of one row names nothing."""
            i = slot * lq + l
            at = first + i
            j = i
            if lq > 1:
                named.append((phys_ref[at],
                              jax.lax.div(off_ref[at], jnp.int32(g))))
            for m in range(l - 1, -1, -1):
                same = jnp.logical_and(named[m][0] == named[l][0],
                                       named[m][1] == named[l][1])
                j = jnp.where(same, i - l + m, j)
            return i, j

        def each_row(act, which=lambda i, j: True):
            """``act(i, j)`` on every in-pool row of the step that
            ``which`` keeps."""
            def slot_rows(slot, carry):
                named = []
                for l in range(lq):
                    i, j = lead(slot, l, named)
                    blk = phys_ref[first + i]
                    ok = jnp.logical_and(blk >= 0, blk < nb)

                    @pl.when(jnp.logical_and(ok, which(i, j)))
                    def _(i=i, j=j):
                        act(i, j)
                return carry
            jax.lax.fori_loop(0, n, slot_rows, 0)

        def leaders(i, j):
            return i == j

        def copies(back: bool, act):
            """``act`` on the K and the V copy of leader ``i``'s group,
            pool to buffer or ``back``."""
            def row(i, j):
                rows = off_ref[first + i] // g * g
                for s, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    group = pool.at[phys_ref[first + i], pl.ds(base, h),
                                    pl.ds(pl.multiple_of(rows, g), g), :]
                    act(pltpu.make_async_copy(buf.at[i], group,
                                              sems.at[2 + s])
                        if back else
                        pltpu.make_async_copy(group, buf.at[i], sems.at[s]))
            return row

        def put(i, j):
            at = jax.lax.broadcasted_iota(jnp.int32, k_buf.shape[1:], 1) \
                == off_ref[first + i] % g
            for new, buf in ((k_new, k_buf), (v_new, v_buf)):
                buf[j] = jnp.where(at, new[i][:, None, :], buf[j])

        def start(c):
            c.start()

        def wait(c):
            c.wait()

        each_row(copies(False, start), leaders)
        each_row(copies(False, wait), leaders)
        each_row(put)
        each_row(copies(True, start), leaders)
        each_row(copies(True, wait), leaders)

    return body


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write_call(k_pool, v_pool, k_new, v_new, phys, off, interpret,
                   head_base=None):
    # ``k_new`` / ``v_new`` [B, L, H, D] in the pools' type, ``phys`` /
    # ``off`` [B, L]; ``head_base`` (int32 [1], traced): the pools hold
    # planes of H heads and the rows go to the one that starts there
    b, lq, h, d = k_new.shape
    nb, _, bs, _ = k_pool.shape
    g = write_group(bs, k_pool.dtype.itemsize)
    # the slots a grid step takes: as many as leave a group a new row of
    # both pools inside the budget, and a divisor of the rows
    fit = _KV_VMEM_BUDGET // (2 * lq * h * g * d * k_pool.dtype.itemsize)
    n = max(c for c in range(1, b + 1) if b % c == 0 and c <= max(fit, 1))
    planed = head_base is not None
    scalars = (phys.reshape(-1), off.reshape(-1)) \
        + ((head_base,) if planed else ())
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((n * lq, h, d), lambda i, *scalars: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b // n,),
        in_specs=[rows, rows, in_hbm, in_hbm],
        out_specs=[in_hbm, in_hbm],
        scratch_shapes=[pltpu.VMEM((n * lq, h, g, d), k_pool.dtype)] * 2
        + [pltpu.SemaphoreType.DMA((4,))])
    return pl.pallas_call(
        _kv_write_body(n, lq, h, g, nb, planed),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype)] * 2,
        # the pools are updated where they lie: operand (scalars first)
        # to result
        input_output_aliases={len(scalars) + 2: 0, len(scalars) + 3: 1},
        # a step's writes land before the next step's reads start
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, k_new.reshape(b * lq, h, d), v_new.reshape(b * lq, h, d),
      k_pool, v_pool)


def paged_kv_write_kernel(k_pool, v_pool, k_new, v_new, phys, off,
                          interpret: bool = False, head_base=None):
    """A chunk's K and V rows into their block pools by ONE in-place
    kernel: ``pool[phys[b, l], head_base + h, off[b, l]] = new[b, h, l]``
    for both pools ``[num_blocks, H_pool, bs, D]`` (float32 or bfloat16,
    one type), ``k_new`` / ``v_new`` ``[B, H, L, D]`` with ``L <=
    MAX_KERNEL_QUERY_CHUNK``, ``phys`` / ``off`` ``[B, L]`` int32.  What
    ``ops.flash_attention.paged_cache_write`` documents, to the bit: an
    index outside the pool is dropped, rows that repeat (the scratch
    block's) land in no defined order.  The pools stay in HBM and are
    aliased input to output; a row travels with its group of rows
    (``_kv_write_body``).  Returns ``(k_pool, v_pool)``."""
    if k_pool.shape != v_pool.shape or k_pool.dtype != v_pool.dtype \
            or k_new.shape != v_new.shape:
        raise InvalidArgumentError(
            "the K and V pools (and the K and V rows) are written by one "
            "kernel and have one shape and type: got %r %s and %r %s"
            % (tuple(k_pool.shape), k_pool.dtype, tuple(v_pool.shape),
               v_pool.dtype))
    if k_new.shape[2] > MAX_KERNEL_QUERY_CHUNK:
        raise InvalidArgumentError(
            "the K/V write kernel takes chunks of at most %d positions, "
            "got %d: a longer chunk is prefill work, the scatter's"
            % (MAX_KERNEL_QUERY_CHUNK, k_new.shape[2]))
    planed = head_base is not None
    if not planed and k_new.shape[1] != k_pool.shape[1]:
        raise InvalidArgumentError(
            "a chunk of %d heads into a pool of %d needs the plane's "
            "head_base" % (k_new.shape[1], k_pool.shape[1]))
    with jax.named_scope("cache_write"):
        return _kv_write_call(
            k_pool, v_pool,
            *(jnp.moveaxis(x, 1, 2).astype(k_pool.dtype)
              for x in (k_new, v_new)),
            jnp.asarray(phys, jnp.int32), jnp.asarray(off, jnp.int32),
            bool(interpret),
            **({"head_base": jnp.reshape(jnp.asarray(
                head_base, jnp.int32), (1,))} if planed else {}))


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
