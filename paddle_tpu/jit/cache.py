"""The cache-layout protocol: every way a decode cache can exist.

PRs 4–17 grew three consumers of the ``gen_decode_cache(layout=...)``
pytree contract — ``DecodeSession`` (aligned batches),
``inference.GenerationPool`` (slot-batched serving) and the PTKV
spill/transfer path — and each of them dispatched on the layout with
``hasattr(c, "table")`` / ``cache_layout == "paged"`` string checks.
That worked while there were exactly two layouts, both positional K/V;
it stops working the moment a model class with a DIFFERENT kind of
decode state arrives (the "Compiler-First State Space Duality and
Portable O(1) Autoregressive Caching" direction in PAPERS.md: a
recurrence carry instead of an attention prefix).

This module names the operations those consumers actually perform as a
:class:`CacheLayout` protocol and registers one singleton per layout.

**The layout is a property of a cache ENTRY, not of the model.**
A decode cache is a flat list of entries (one a layer in most models; a
layer that keeps K/V AND a state of constant size, ``nn.CCAttention``, owns
two, side by side), and an entry's own type says which layout it is
(:func:`entry_layout`: a ``table`` field is paged, a ``limit`` field
recurrent, else dense).  Every hook below is written
for ONE entry (``*_entry``), and the list-level hooks the consumers call
walk the list and hand each entry to its own layout.  A model whose
layers all keep one kind gets the registered singleton
(:func:`layout_of` returns it, so nothing changes for them); a model
that mixes kinds (``models.HybridMambaLM``: 26 recurrent entries and 2
paged ones in one list) gets a :class:`ComposedLayout`, whose
capabilities are the MEET of its entries': it has a block table if any
entry has one, addresses positions only if every entry does, spills only
if every entry spills.  The consumers ask the layout, never a string.

==============  ==========================  ================================
kind             an entry's type says so by  what a position keeps a layer
==============  ==========================  ================================
``dense``        neither field below         ``k``, ``v`` ``[B, H, S, D]``
``paged``        a ``table``                 ``k``, ``v`` blocks by head,
                                             ``[num_blocks, H, bs, D]``
``latent``       a ``table`` and a           ONE compressed latent, no head:
                 ``latent`` field            ``latent [num_blocks, bs, W]``
``window``       a ``table`` and a           ``k``, ``v`` blocks by head for
                 ``window`` field            the last ``window`` positions
                                             only: the table is a RING of
                                             ``window / bs + 1`` blocks
``recurrent``    a ``limit``                 nothing: a state of constant size
==============  ==========================  ================================

What an entry holds besides its bookkeeping (``index``, ``table``,
``limit``) are its PAYLOAD fields (:meth:`CacheLayout.payload_fields`):
the splice, the in-memory spill, the dtype stamp and the byte figures
walk them, whatever they are called, so the latent kind needs no second
allocator, table mask or preempt path: it is one more kind of paged entry.

==================  =====================================================
operation            who calls it / what it decides
==================  =====================================================
``begin_prefill``    DecodeSession._prefill — layout-specific cache prep
                     BEFORE the forward (the recurrent layout clamps its
                     update window to the true prompt length so padded
                     bucket positions are identity steps; positional
                     layouts need nothing — pad K/V is simply never
                     attended)
``finalize_prefill`` DecodeSession._prefill — commit the true length
                     after the forward (all layouts set ``index``; the
                     recurrent layout also re-opens its update window)
``insert_row``       GenerationPool._insert — splice a batch-1 prefilled
                     row cache into a pool slot (traced; ONE compile)
``begin_step``       GenerationPool._pool_decode — layout prep before the
                     batched step (the recurrent layout closes the update
                     window of inactive slots: a recurrence updates every
                     row every step, and a closed window makes the row an
                     identity step, so no state is ever copied back)
``freeze_step``      GenerationPool._pool_decode — merge a decode step's
                     cache for INACTIVE slots back to the pre-step value
                     (the index; the recurrent layout its window too)
``field_axes``       DecodeMesh.place_cache — PartitionSpec axes per
                     cache field (k/v shard ('dp','mp'); a recurrence
                     state shards ('dp', None): slots over dp, the state
                     vector replicated within an mp group)
``cache_dtype_str``  cache_stats()/config_fingerprint() provenance — the
                     payload dtype without assuming a ``.k`` field
``bytes_per_slot_by_kind``  cache_stats() — entries and the decode-state
                     HBM one slot pins at full span, by the entries'
                     kind: the figure the slots-per-GB capacity
                     comparison is made of
``fingerprint_extra``  config_fingerprint() — layout-private geometry
                     (paged: block_size/num_blocks; recurrent: d_state)
                     so the PTKV fingerprint check can never let one
                     model class adopt another's spill file
==================  =====================================================

Capability flags gate the serving features that CANNOT transfer across
layouts, so a pool kwarg that silently no-ops is impossible:

- ``positional``: the cache addresses individual past positions.
  Chunked prefill, prefix sharing and speculative verify-rewind all
  require it; the recurrent layout folds history into one carry, so
  those knobs raise typed errors at construction naming the layout.
- ``paged``: the cache is a block pool behind a table (allocator,
  scratch-block masking, block-granular spill live in the pool — they
  are paged POLICY, not protocol).
- ``spillable``: preempt/resume/adopt can move a slot's state through
  the host tier.
- ``transferable``: the state also goes through the disk tier and the
  PTKV transfer contract, whose file carries ONE kind of entry (a composed
  layout is not).
- ``recurrent``: some entry is a state of constant size (it has a
  ``limit`` window, a ``state_bytes`` figure and no block).
- ``windowed``: some entry is a ring of blocks (``WindowLayout``): a
  chunk of several positions that starts mid-way is refused.

The traced-method bodies (``insert_row``/``freeze_step``/the prefill
hooks) are the EXACT code the pool and session inlined before this
module existed — re-registering the dense/paged layouts against the
protocol changes no jaxpr, so the byte-identity and compile-count pins
across the serving suite hold unmodified.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError

__all__ = ["CacheLayout", "DenseLayout", "PagedLayout", "LatentLayout",
           "WindowLayout", "RecurrentLayout",
           "ComposedLayout", "CACHE_LAYOUTS", "get_layout", "entry_layout",
           "layout_of"]


class CacheLayout:
    """One decode-cache layout's operations and capabilities.

    Subclasses are stateless singletons (all state lives in the cache
    pytree and the pool); methods marked *traced* run inside jitted
    bodies and must keep the exact semantics the compile-count pins
    were taken against.
    """

    #: registry key and the ``cache_layout=`` string users pass
    name: str = "?"
    #: cache addresses individual past positions (prefix tree, chunked
    #: prefill and speculative rewind are only meaningful here)
    positional: bool = True
    #: cache is a block pool behind a per-slot table
    paged: bool = False
    #: preempt/resume can move per-slot state through the host tier
    spillable: bool = False
    #: ... and through the disk tier and PTKV transfer files
    transferable: bool = False
    #: some entry is a state of constant size
    recurrent: bool = False
    #: some entry has its prompt form (the latent entry's expanded path)
    #: only for a chunk that starts at position 0: what starts a prompt's
    #: chunk mid-way (chunked prefill, prefix sharing) is refused
    prompt_from_zero: bool = False
    #: some entry is a ring of blocks (``WindowLayout``)
    windowed: bool = False

    @staticmethod
    def payload_fields(entry) -> tuple:
        """The fields of ``entry`` that hold cache content, in the entry's
        own order: every field but the bookkeeping (``index``, ``table``,
        ``limit``) that is not None (a float K/V entry's scales are)."""
        return tuple(f for f in entry._fields
                     if f not in ("index", "table", "limit", "window")
                     and getattr(entry, f) is not None)

    def layouts(self, cache) -> tuple:
        """The layout of every entry of ``cache``, in order (a singleton:
        itself throughout)."""
        return (self,) * len(cache)

    def entries(self, cache, kind: str) -> list:
        """The entries of ``cache`` whose own layout is ``kind``."""
        return [c for lay, c in zip(self.layouts(cache), cache)
                if lay.name == kind]

    def recurrent_entries(self) -> str:
        """How a refusal names the entries that are a recurrent state (a
        layer may own more than one entry: what is counted is entries)."""
        return "every cache entry is a state of constant size"

    def not_transferable(self) -> str:
        """How a refusal says why this layout has no disk tier and no PTKV
        transfer."""
        return "has no per-slot granularity to write"

    def not_spillable(self) -> str:
        """How a refusal says why a slot of this layout cannot be preempted
        into the host tier."""
        return ("a dense pool has no spill granularity — use "
                "cache_layout='paged' (or 'recurrent')")

    # -- prefill hooks (traced) ------------------------------------------
    def begin_prefill_entry(self, c, true_len):
        """Layout prep before the prefill forward (identity for
        positional layouts: pad K/V is written but never attended)."""
        return c

    def finalize_prefill_entry(self, c, true_len, max_len):
        """Commit the true prompt length after the prefill forward."""
        return c._replace(index=true_len)

    def begin_prefill(self, cache, true_len):
        return [lay.begin_prefill_entry(c, true_len)
                for lay, c in zip(self.layouts(cache), cache)]

    def finalize_prefill(self, cache, true_len, max_len):
        return [lay.finalize_prefill_entry(c, true_len, max_len)
                for lay, c in zip(self.layouts(cache), cache)]

    # -- pool splice / step freeze (traced) ------------------------------
    def insert_entry(self, cp, cr, slot, length, blocks=None):
        raise NotImplementedError

    def begin_step_entry(self, c, active):
        """Layout prep before a pool's batched decode step (identity
        here; the paged pool masks its tables itself; the recurrent
        layout closes the update window of the free slots)."""
        return c

    def freeze_step_entry(self, c, old, active):
        """Merge a decode step's cache back to the pre-step value for
        inactive slots (positional layouts: only the index advances
        per step, so only the index needs freezing)."""
        return c._replace(index=jnp.where(active, c.index, old.index))

    def insert_row(self, pool_cache, row_cache, slot, length, blocks=None):
        return [lay.insert_entry(cp, cr, slot, length, blocks)
                for lay, cp, cr in zip(self.layouts(pool_cache), pool_cache,
                                       row_cache)]

    def begin_step(self, cache, active):
        return [lay.begin_step_entry(c, active)
                for lay, c in zip(self.layouts(cache), cache)]

    def freeze_step(self, new_cache, prev_cache, active):
        return [lay.freeze_step_entry(c, old, active)
                for lay, c, old in zip(self.layouts(new_cache), new_cache,
                                       prev_cache)]

    # -- placement / accounting ------------------------------------------
    def field_axes(self, field: str):
        """PartitionSpec axes for one cache field on a dp×mp
        :class:`~paddle_tpu.jit.mesh.DecodeMesh`."""
        if field in ("k", "v", "k_scale", "v_scale"):
            return ("dp", "mp")
        if field in ("table", "index"):
            return ("dp",)
        raise InvalidArgumentError(
            "unknown decode-cache field %r for layout %r"
            % (field, self.name))

    def entry_dtype_str(self, c) -> str:
        return str(np.dtype(getattr(c, self.payload_fields(c)[0]).dtype))

    def cache_dtype_str(self, cache) -> str:
        """Payload dtype as provenance (``cache_stats`` /
        ``config_fingerprint`` stamp this): every entry's own, each
        named once."""
        return "+".join(dict.fromkeys(
            lay.entry_dtype_str(c)
            for lay, c in zip(self.layouts(cache), cache)))

    def entry_bytes_per_slot(self, c, slots: int, max_len: int) -> int:
        """Decode-state bytes ONE slot pins at full span in this entry.
        For the positional layouts this is the dense-equivalent per-slot
        K/V slab (scales included): what admitting one more concurrent
        request costs in HBM when every request can run to max_len."""
        total = 0
        for field in self.payload_fields(c):
            a = getattr(c, field)
            per_tok = int(np.prod(a.shape)) * a.dtype.itemsize
            # dense: [slots, H, max_len, D] -> bytes / slots.
            # paged: [blocks, H, bs, D] -> bytes-per-token * max_len
            if self.paged:
                tokens = int(a.shape[0]) * int(a.shape[self.block_axis])
                total += per_tok // tokens * max_len
            else:
                total += per_tok // int(slots)
        return total

    def bytes_per_slot_by_kind(self, cache, slots: int, max_len: int) -> dict:
        """``{layout name: (entries, bytes a slot)}`` over the entries (a
        layer that owns two entries counts under each one's kind)."""
        out: dict = {}
        for lay, c in zip(self.layouts(cache), cache):
            n, b = out.get(lay.name, (0, 0))
            out[lay.name] = (n + 1, b + lay.entry_bytes_per_slot(
                c, slots, max_len))
        return out

    def fingerprint_extra(self, pool) -> dict:
        """Layout-private geometry for ``config_fingerprint()`` — keys
        the PTKV/journal fingerprint comparison treats as identity, so
        cross-layout (and cross-geometry) adoption is impossible."""
        return {}


class DenseLayout(CacheLayout):
    """Preallocated ``[slots, H, max_len, D]`` K/V per slot."""

    name = "dense"

    def insert_entry(self, cp, cr, slot, length, blocks=None):
        # every payload field of the row (K/V and an int8 cache's scales,
        # or a latent): the slot's slab whole
        upd = {f: getattr(cp, f).at[slot].set(
                   getattr(cr, f)[0].astype(getattr(cp, f).dtype))
               for f in self.payload_fields(cp)}
        return cp._replace(
            index=cp.index.at[slot].set(jnp.asarray(length, jnp.int32)),
            **upd)


class PagedLayout(CacheLayout):
    """Fixed-size K/V blocks addressed through a per-slot table; the
    allocator (free lists, refcounted prefix sharing, scratch-block
    masking, block-granular spill) is pool policy layered on top."""

    name = "paged"
    paged = True
    spillable = True
    transferable = True
    #: the axis of a payload field that runs over a block's positions
    #: (``[num_blocks, H, bs, D]``; the scales lack the last)
    block_axis = 2

    def insert_entry(self, cp, cr, slot, length, blocks=None):
        # the row cache is an identity-tabled batch-1 pool (row block
        # 1+j holds logical block j), so the splice is ONE scatter a
        # payload field copying every logical block to the physical ids
        # in ``blocks``; entries past the reservation are 0, harmlessly
        # dumping their pad-garbage blocks into the scratch block.  An
        # int8 cache's per-block scales are payload like K/V: they
        # splice with their blocks (same ids), so a spliced block can
        # never be read under another request's scale
        upd = {f: getattr(cp, f).at[blocks].set(
                   getattr(cr, f)[1:].astype(getattr(cp, f).dtype))
               for f in self.payload_fields(cp)}
        return cp._replace(
            table=cp.table.at[slot].set(blocks),
            index=cp.index.at[slot].set(jnp.asarray(length, jnp.int32)),
            **upd)

    def fingerprint_extra(self, pool) -> dict:
        return {"block_size": pool._block_size,
                "num_blocks": pool._num_blocks}


class LatentLayout(PagedLayout):
    """One more kind of paged entry: the compressed latent of latent
    attention (``nn.LatentAttention``), ONE vector a position shared by
    every head, in blocks ``latent [num_blocks, bs, W]`` (the K/V latent
    after its norm, the one rotary key head after its turn, zeros up to
    whole 128-lane tiles), behind the same ``table`` and ``index`` as K/V.
    The allocator, the scratch-block masking, ``live_blocks`` and preempt
    and resume in memory are the paged layout's, unchanged.

    What it does not carry, each refused by a typed error that names the
    latent entry: an int8 pool (no scale has a head to ride with:
    ``payload_dtypes``), the disk tier and PTKV transfer (their files hold
    K/V blocks by head: not ``transferable``), and prefix sharing and
    chunked prefill (``prompt_from_zero``: only a chunk known to start at
    position 0 is attended in the EXPANDED form over its own keys; one that
    starts mid-way runs absorbed through the XLA composition, whose scores
    are ``[rows, heads, chunk, context]``: right, and not sized for a
    prompt's chunks)."""

    name = "latent"
    transferable = False
    prompt_from_zero = True
    #: the element types a latent can be stored in (``nn.LatentAttention
    #: .gen_decode_cache`` refuses the others)
    payload_dtypes = ("float32", "bfloat16")
    #: ``[num_blocks, bs, W]``: no head axis
    block_axis = 1

    def not_transferable(self) -> str:
        return ("keeps a latent entry a layer (one latent a position, no "
                "head axis), which the file format does not hold")

    def field_axes(self, field: str):
        if field == "latent":
            # slots' blocks over dp; the latent has no head to shard
            # over mp: every mp shard reads the whole of it
            return ("dp", None)
        if field in ("table", "index"):
            return ("dp",)
        raise InvalidArgumentError(
            "unknown decode-cache field %r for layout 'latent'" % (field,))


class WindowLayout(PagedLayout):
    """One more kind of paged entry: the K/V of a WINDOW layer
    (``nn.GroupedQueryAttention(window=)``), blocks by head as the paged
    kind holds them, behind a ``table`` ``[slots, ring]`` that is a RING:
    position ``p`` lives at entry ``(p // bs) % ring``, ``ring = window /
    bs + 1``, so a block behind the window is overwritten by a later one,
    never freed, and a slot pins ``ring`` blocks whatever its context or
    ``max_len``.

    **The ring's blocks are the slot's own**, fixed when the entry is made
    (slot ``s`` owns blocks ``1 + s * ring ...`` of the entry's own pools,
    block 0 the scratch): the worst case of a window pool is ``slots x
    ring`` blocks, small enough to hold outright, and a free list that can
    never refuse is bookkeeping.  So the allocator, ``_blocks_needed``, the
    free list and admission stay the PAGED entries' alone; what the pool
    does to a paged entry's table a step (an inactive slot's row routed to
    the scratch block, the row restored after) it does to this one's.

    ``insert_entry`` copies a prefilled row's LAST ring of blocks to their
    ring places and no others.  What the kind does not carry, each refused
    by a typed error that names the window entry: preempt and resume (not
    ``spillable``: a ring has no block list to park), the disk tier and
    PTKV transfer, an int8 pool, prefix sharing (a shared prefix's blocks
    behind the window do not exist), chunked prefill and speculative
    decoding (``windowed``: a chunk that starts mid-way may ask for keys
    the ring overwrote), and ``mp`` / ``dp`` meshes."""

    name = "window"
    spillable = False
    transferable = False
    windowed = True

    def not_transferable(self) -> str:
        return ("keeps a window entry (a ring of blocks a slot, in pools "
                "of its own), which the file format does not hold")

    def not_spillable(self) -> str:
        return ("a window entry keeps a slot's last positions in a ring of "
                "blocks of its own, which no block list names: preempt and "
                "resume of a window entry are not built")

    def insert_entry(self, cp, cr, slot, length, blocks=None):
        # the row was prefilled into a ring that spans it whole (never
        # wrapped: ``gen_decode_cache(num_blocks=None)``), so its logical
        # block ``j`` is found through its own table.  Ring place ``c`` of
        # the slot takes the LAST logical block at or under the row's top
        # block that is ``c`` modulo the ring; a place no block has
        # reached yet takes the row's scratch block, which nothing reads
        # (the walk starts at the band's first entry).  The slot's places
        # are ``ring`` consecutive blocks: one gather from the row, one
        # slice written where the pool lies
        import jax

        from ..ops.flash_attention import ring_blocks_held

        ring, bs = cp.table.shape[1], cp.k.shape[2]
        length = jnp.asarray(length, jnp.int32)
        logical = ring_blocks_held(jnp.maximum(length - 1, 0) // bs, ring)
        src = jnp.where(logical >= 0,
                        cr.table[0][jnp.maximum(logical, 0)
                                    % cr.table.shape[1]], 0)
        first = cp.table[slot, 0]
        upd = {f: jax.lax.dynamic_update_slice_in_dim(
                   getattr(cp, f),
                   getattr(cr, f)[src].astype(getattr(cp, f).dtype),
                   first, axis=0)
               for f in self.payload_fields(cp)}
        return cp._replace(
            index=cp.index.at[slot].set(length), **upd)

    def entry_bytes_per_slot(self, c, slots: int, max_len: int) -> int:
        # the ring, whatever ``max_len``: the point of the kind
        ring = int(c.table.shape[1])
        return sum(int(np.prod(getattr(c, f).shape[1:]))
                   * getattr(c, f).dtype.itemsize * ring
                   for f in self.payload_fields(c))

    def field_axes(self, field: str):
        if field == "window":
            return ()  # the band's width: a scalar, replicated
        return super().field_axes(field)

    def fingerprint_extra(self, pool) -> dict:
        first = pool._layout.entries(pool._cache, self.name)[0]
        return {"window": int(first.window),
                "ring": int(first.table.shape[1])}


class RecurrentLayout(CacheLayout):
    """Constant-size recurrence carry: O(1) state per token, no block
    table, no paging, no prefix tree.  Three caches live on it:
    ``nn.ssm.RecurrentDecodeCache`` (``state [B, d_state]``),
    ``nn.RetentionDecodeCache`` (``state [B, Hkv, dv, D]`` and ``norm [B,
    Hkv, 1, D]``) and ``nn.MambaDecodeCache`` (``conv [B, (K-1) * C]`` and
    ``ssm [B, N, C]``).  Every field of a layer's cache but ``index`` and
    ``limit`` is a STATE FIELD, slots leading (:meth:`state_fields`): the
    splice, the spill, the placement and the accounting run over them
    all, whatever they are called, so a cache of another shape needs no
    code here.

    ``limit`` is the layout's pad-garbage discipline.  A positional
    cache can write garbage K/V for padded bucket positions because the
    index keeps them from ever being ATTENDED; a recurrence has no such
    afterthought — every update folds into the one carry forever.  So
    the prefill hook narrows the update window to the true prompt
    length (positions past it are identity steps), and finalize re-opens
    it to max_len for decode.  A pool's step closes it on its free slots
    (:meth:`begin_step_entry`): the rows the step must not move are
    identity steps INSIDE the recurrence, so nothing of the size of the
    pool's state is ever selected or copied to put them back.
    """

    name = "recurrent"
    positional = False
    spillable = True
    transferable = True
    recurrent = True

    @staticmethod
    def state_fields(layer_cache) -> tuple:
        return tuple(f for f in layer_cache._fields
                     if f not in ("index", "limit"))

    def begin_prefill_entry(self, c, true_len):
        # the window narrows to the true length; and a cache type that
        # asks for it (``empty_as_none``) is told STATICALLY that the
        # prefill starts from nothing: its state fields go in as None,
        # so a layer need not read (or multiply by) a state of zeros
        c = c._replace(limit=true_len)
        if getattr(c, "empty_as_none", False):
            c = c._replace(**{f: None for f in self.state_fields(c)})
        return c

    def finalize_prefill_entry(self, c, true_len, max_len):
        return c._replace(index=true_len,
                          limit=jnp.asarray(max_len, jnp.int32))

    def begin_step_entry(self, c, active):
        """The update window of a pool's step, per slot: a free slot's is
        closed, so its row is an identity step of the recurrence."""
        return c._replace(limit=jnp.where(active, c.limit, 0))

    def insert_entry(self, cp, cr, slot, length, blocks=None):
        upd = {f: getattr(cp, f).at[slot].set(
                   getattr(cr, f)[0].astype(getattr(cp, f).dtype))
               for f in self.state_fields(cp)}
        return cp._replace(
            index=cp.index.at[slot].set(jnp.asarray(length, jnp.int32)),
            **upd)

    def freeze_step_entry(self, c, old, active):
        # the recurrence updates EVERY row's carry every step, and an
        # inactive slot's update would fold its stale last token into
        # state a resumed/refilled request then inherits.  It did not:
        # ``begin_step`` closed those rows' windows, so their carry came
        # through the step untouched.  What is put back is the index and
        # the window itself
        return c._replace(index=jnp.where(active, c.index, old.index),
                          limit=old.limit)

    def field_axes(self, field: str):
        if field == "index":
            return ("dp",)
        if field == "limit":
            return ()  # scalar window bound: replicated
        if field in ("k", "v", "k_scale", "v_scale", "table"):
            raise InvalidArgumentError(
                "unknown decode-cache field %r for layout 'recurrent'"
                % (field,))
        # a state field, whatever it is called: slots over dp; a slot's
        # state stays whole (replicated within an mp group)
        return ("dp", None)

    def entry_dtype_str(self, c) -> str:
        return "+".join(dict.fromkeys(
            str(np.dtype(getattr(c, f).dtype))
            for f in self.state_fields(c)))

    def entry_bytes_per_slot(self, c, slots: int, max_len: int) -> int:
        # constant in max_len — the whole point
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize // int(slots)
                   for a in (getattr(c, f) for f in self.state_fields(c)))

    def fingerprint_extra(self, pool) -> dict:
        # the shape of every state field of a slot: a toy recurrence's
        # spill file ([d_state]) can never be adopted by a model whose
        # state is a matrix a head, nor the other way round
        first = pool._layout.entries(pool._cache, self.name)[0]
        fields = self.state_fields(first)
        return {"d_state": int(getattr(first, fields[0]).shape[-1]),
                "state_shapes": [list(getattr(first, f).shape[1:])
                                 for f in fields]}


class ComposedLayout(CacheLayout):
    """The layout of a cache whose entries are not all of one kind: the
    entries' own layouts in order, and the meet of their capabilities.
    Built by :func:`layout_of` from the cache a model hands out; there is
    nothing to register and nothing a user names."""

    def __init__(self, layouts):
        self._layouts = tuple(layouts)
        kinds = sorted({lay.name for lay in self._layouts})
        self.name = "+".join(kinds)
        self.positional = all(lay.positional for lay in self._layouts)
        self.paged = any(lay.paged for lay in self._layouts)
        self.spillable = all(lay.spillable for lay in self._layouts)
        self.recurrent = any(lay.recurrent for lay in self._layouts)
        self.prompt_from_zero = any(lay.prompt_from_zero
                                    for lay in self._layouts)
        self.windowed = any(lay.windowed for lay in self._layouts)
        # a PTKV file carries blocks OR state rows, never both
        self.transferable = False

    def layouts(self, cache) -> tuple:
        if len(cache) != len(self._layouts):
            raise InvalidArgumentError(
                "a cache of %d entries under a layout composed for %d"
                % (len(cache), len(self._layouts)))
        return self._layouts

    def recurrent_entries(self) -> str:
        at = [i for i, lay in enumerate(self._layouts) if lay.recurrent]
        return ("%d of the %d cache entries are states of constant size "
                "(entries %s%s)" % (len(at), len(self._layouts),
                           ", ".join(map(str, at[:4])),
                           ", ..." if len(at) > 4 else ""))

    def not_transferable(self) -> str:
        if self.recurrent:
            return "has both (%s)" % self.recurrent_entries()
        if self.windowed:
            return _WINDOW.not_transferable()
        return "mixes kinds of entry (%s)" % self.name

    def not_spillable(self) -> str:
        return "; ".join(dict.fromkeys(
            lay.not_spillable() for lay in self._layouts
            if not lay.spillable))

    def fingerprint_extra(self, pool) -> dict:
        out = {}
        for lay in dict.fromkeys(self._layouts):
            out.update(lay.fingerprint_extra(pool))
        return out


CACHE_LAYOUTS = {
    layout.name: layout
    for layout in (DenseLayout(), PagedLayout(), RecurrentLayout())
}

# no string a caller passes: ``cache_layout="paged"`` gives a model that
# keeps latents its latent entries, and the entry's type says the rest
_LATENT = LatentLayout()
# nor this: a layer built with ``window=`` hands out window entries
_WINDOW = WindowLayout()


def get_layout(name: str) -> CacheLayout:
    """The registered :class:`CacheLayout` singleton for ``name``; a
    typed error naming the registry otherwise — the single validation
    every cache consumer (session, pool) routes through."""
    layout = CACHE_LAYOUTS.get(name)
    if layout is None:
        raise InvalidArgumentError(
            "cache_layout must be one of %s, got %r"
            % (sorted(CACHE_LAYOUTS), name))
    return layout


def entry_layout(entry) -> CacheLayout:
    """The layout of ONE layer's cache entry, from its type: a block
    ``table`` is paged (latent where what the blocks hold is a ``latent``,
    window where the table is a ring: a ``window`` field), an update
    window ``limit`` recurrent, else dense (K/V, or a latent by
    slot)."""
    fields = getattr(entry, "_fields", ())
    if "table" in fields:
        if "window" in fields:
            return _WINDOW
        return _LATENT if "latent" in fields else CACHE_LAYOUTS["paged"]
    if "limit" in fields:
        return CACHE_LAYOUTS["recurrent"]
    return CACHE_LAYOUTS["dense"]


def layout_of(cache) -> CacheLayout:
    """The layout of a whole cache list: the registered singleton where
    every entry is of one kind, a :class:`ComposedLayout` otherwise."""
    layouts = [entry_layout(c) for c in cache]
    if len(set(layouts)) == 1:
        return layouts[0]
    return ComposedLayout(layouts)
