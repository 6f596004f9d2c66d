"""Slot-based continuous batching over the KV-cached decode engine.

``GenerationPool`` is the serving front of ``jit.DecodeSession``: N cache
SLOTS share ONE batched decode step (the slot-batched ``DecodeCache``
layout whose index is a per-row ``[slots]`` vector), concurrent requests
are packed into the slots, and a slot freed by a finished sequence is
refilled from the request queue — so throughput stays at the batched
decode rate regardless of request length skew, the continuous-batching
scheme production LLM servers use (PAPERS.md: compiler-first O(1)
autoregressive caching; the batching analog of the reference's
``PredictorPool``, which multiplexes predictors rather than cache slots).

Dataflow per ``step()``, the host one step behind the device
(docs/DESIGN.md §5t):

1. one batched decode dispatch advances EVERY live slot a token, from
   the token vector the step in flight leaves on the device; inactive
   slots are masked — their cache index does not advance;
2. the sampled token ids of the step BEFORE it (the only host
   round-trip, which returns while the new step runs) are appended
   per-request; rows hitting EOS or their token budget release the slot;
3. free slots are refilled under the step in flight: each queued
   request runs a BUCKETED batch-1 prefill (compiled once per bucket,
   shared with every later request), and its row cache is spliced into
   the slot by a tiny jitted insert (slot id is a traced scalar — one
   compile total); its first token stays on the device and rides the
   next download.

``cache_layout="paged"`` swaps the dense per-slot K/V slabs for the
vLLM block-table scheme (docs/DESIGN.md §5b): K/V live in a global pool
of fixed-size blocks, each slot owns a row of a ``[slots, max_blocks]``
block table, and the pool runs a host-side FREE-LIST allocator — a
request reserves its worst-case block span at admission (so decode never
runs out mid-request), the FIFO head defers when blocks are scarce, and
``_finish`` returns blocks for reuse.  Cache HBM then scales with the
token budget (``num_blocks``), not max_len × slots, while every shape
stays static and greedy results stay token-identical to dense.

Two paged-only extensions ride the allocator (docs/DESIGN.md §5i):

- ``prefill_chunk_tokens=C`` replaces the one-shot bucketed prefill
  with ONE fixed-shape chunk executable: each tick spends at most C
  tokens of prompt work (one padded ``[C]`` chunk through the per-slot
  table-addressed write path) before the batched decode step runs, so
  a long prompt can no longer monopolize a tick — TTFT of the long
  prompt and inter-token latency of every resident request are both
  bounded.  Chunk K/V land through the SAME attention/masking
  discipline as decode, so position ``p``'s K/V are bit-identical
  however the prompt is chunked (masked contributions are exactly
  zero; per-position projections see only position ``p``).
- ``prefix_sharing=True`` makes the allocator REFCOUNT-aware and keeps
  a hash-keyed prefix index over resident FULL prompt blocks (key =
  hash of the block's token ids chained on the parent block's key).
  Admission matches an incoming prompt against the longest resident
  prefix, maps those physical blocks into the new slot's table
  READ-ONLY (refcount bumped; a shared block is full and writes only
  ever land at positions past the matched prefix, in the request's own
  freshly allocated blocks — copy-on-write by construction), and
  chunk-prefills only the unmatched suffix.  Greedy output is
  byte-identical to a sharing-off run; release/cancel/reset decref
  instead of free, and ``cache_stats()`` counts shared blocks once.

Traffic-grade scheduling rides the same allocator (docs/DESIGN.md §5j):

- ``submit()`` takes ``priority=`` / ``tenant=`` / ``deadline=``
  scheduling metadata, and ``_refill`` picks the next request to admit
  by ``(priority desc, deadline asc, arrival)`` instead of strict
  FIFO, with an optional per-tenant slot cap (``tenant_slot_cap=``) so
  one tenant's burst cannot monopolize the pool.  The block-wait
  discipline is preserved per the CHOSEN candidate: when the best
  candidate cannot reserve its blocks, admission waits rather than
  skipping ahead — no starvation within the declared ordering.
- ``preempt(rid)`` evicts one actively-decoding request mid-flight by
  SPILLING its K/V to a host-RAM block pool — a second tier under the
  free-list allocator.  The victim's written blocks are downloaded in
  one batched ``device_get`` (int8 scales ride along), its device
  blocks move to a reclaimable SPILLED tier (content intact — the
  free/resident/spilled/scratch partition is exact:
  ``free + resident + spilled + scratch == num_blocks``), and the
  allocator reclaims spilled device copies lazily, only when an
  allocation actually needs them (the host copy is the survivor).
  Resume (driven by ``_refill`` under the same priority ordering)
  re-maps still-resident spilled blocks in place — zero copy — and
  uploads host copies into fresh blocks for anything reclaimed, then
  restores the slot's table row, cache index and last-token input:
  greedy decode continues BYTE-IDENTICALLY to an uninterrupted run
  (K/V are restored bit-exact, and prompt + committed tokens determine
  greedy state — the O(1)-cache contract).  Spill and resume are
  eager host-side array ops: no tracked executable is touched, so
  ``compile_counts()`` is unchanged across preemption (test-pinned).
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import (AlreadyExistsError, InvalidArgumentError,
                           NotFoundError, PreconditionNotMetError)
from ..jit import aot
from ..jit.cache import get_layout
from ..jit.decode import (DecodeSession, check_sampling, classify_finish,
                          make_sampling_state, sample_logits_data)
from ..nn import functional as F
from ..nn import lora as _lora_mod
from ..nn.layer.moe import SparseExperts
from ..ops.flash_attention import decode_route, paged_kv_write_route
from ..jit.mesh import DecodeMesh

__all__ = ["GenerationPool", "kv_reachable_bytes",
           "DuplicateRequestError"]

# the serving fault plane, bound lazily: importing paddle_tpu.serving at
# module scope here would be circular (serving.engine imports this
# module), and the late bind keeps standalone pool users import-clean —
# the first step()/refill pays one sys.modules lookup, after which
# _fire is a bound-module attribute call that no-ops while no plane is
# installed (see serving/faults.py)
_faults = None


def _fire(point: str) -> None:
    global _faults
    if _faults is None:
        from ..serving import faults as _faults_mod
        _faults = _faults_mod
    _faults.fire(point)


def _pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (>= 1).  The spill tier pads its
    eager gather/scatter index vectors to these buckets so preempting
    victims of every length compiles O(log max_blocks) eager shapes,
    not one per distinct written-block count."""
    p = 1
    while p < n:
        p <<= 1
    return p


# the serving trace plane, bound lazily for the same circularity reason
# as _faults above: _trace_active() costs one bound-module attribute
# read returning None while no tracer is installed, so the tick phases
# below pay nothing when tracing is off (serving/trace.py)
_trace = None


def _trace_active():
    global _trace
    if _trace is None:
        from ..serving import trace as _trace_mod
        _trace = _trace_mod
    return _trace.active()


_NO_SPAN = contextlib.nullcontext()


def tick_phase(tr, name: str, meta=None):
    """The context one phase of a tick runs in: ``tr.span(name,
    **meta())`` under the tracer ``tr``, one shared no-op when ``tr`` is
    None.  ``meta`` is a callable, so with tracing off no span is made
    and no meta is built.  The engine spans its own phases with it."""
    if tr is not None:
        return tr.span(name, **meta()) if meta is not None \
            else tr.span(name)
    return _NO_SPAN


_transfer = None


def _transfer_mod():
    # same lazy binding as _fire/_trace_active: the K/V transfer
    # contract lives in the serving layer, and importing it at module
    # load would cycle (serving.engine imports this module)
    global _transfer
    if _transfer is None:
        from ..serving import transfer as _transfer_module
        _transfer = _transfer_module
    return _transfer


class DuplicateRequestError(AlreadyExistsError, InvalidArgumentError):
    """``submit()`` reused a request_id that is still queued, active, or
    awaiting collection.  Subclasses ``InvalidArgumentError`` so callers
    that catch the broad class keep working, while retry loops can catch
    the duplicate specifically (a duplicate means the caller's id
    bookkeeping is wrong — retrying the same id cannot succeed)."""


def kv_reachable_bytes(tokens, max_len: int, num_layers: int,
                       num_heads: int, head_dim: int,
                       layout: str = "dense", block_size: int = 32,
                       dtype="float32") -> int:
    """KV-cache bytes a decode step can actually READ for the given
    per-row token counts (``tokens``: an int or a sequence, one entry
    per slot/row).

    Dense preallocation reaches ``rows * max_len`` positions whatever
    the real occupancy; the paged layout reaches only the MAPPED blocks,
    ``sum(ceil(t / block_size)) * block_size`` positions capped at
    ``max_len`` per row (the reserved scratch block is excluded, and so
    is a ragged final block's over-hang past max_len: both can be
    gathered but every read of them is masked, so they never feed a
    softmax — the cap keeps the "paged <= dense below full occupancy"
    contract even for block sizes that do not divide max_len).  This is
    the quantity the ROADMAP item names — cache HBM scaling with actual
    tokens, not max_len × slots — and what ``cache_stats()`` reports
    per layout.

    ``dtype="int8"`` (the quantized cache) counts the TRUE bytes: int8
    K/V plus the per-head fp32 scales that ride alongside (4 bytes per
    K and per V head-position) — the honest number is what makes the
    "int8 halves cache bandwidth" claim auditable from the artifact."""
    toks = [int(t) for t in
            (tokens if hasattr(tokens, "__len__") else [tokens])]
    # per-head scale overhead only exists for the quantized cache
    scale_bytes = 4 if np.dtype(dtype) == np.dtype(np.int8) else 0
    per_token = 2 * num_layers * num_heads * \
        (head_dim * np.dtype(dtype).itemsize + scale_bytes)
    if layout == "dense":
        return len(toks) * int(max_len) * per_token
    if layout != "paged":
        raise InvalidArgumentError(
            "layout must be 'dense' or 'paged', got %r" % (layout,))
    bs = int(block_size)
    return sum(min(-(-t // bs) * bs, int(max_len))
               for t in toks) * per_token

# per-request sampling config, resolved at the submit edge and carried
# as DATA through the whole request lifecycle — slot, spill file,
# journal record, PTKV migration header — so a preempted/migrated
# sampled request resumes under ITS OWN config (docs §5q).  ``seed`` is
# always a resolved int: row streams are fold_in(PRNGKey(seed), step)
# with step = tokens already sampled, a pure function of the request.
# ``draws`` is the stream offset at THIS submission — 0 for a fresh
# request; a resubmission of prompt+committed passes the committed
# count, so the re-prefill's draw lands at exactly the step the
# original continuation would have used and the stream never restarts.
_SamplingConfig = collections.namedtuple(
    "_SamplingConfig", ["temperature", "top_k", "top_p", "seed",
                        "draws"], defaults=(0,))

# scheduling metadata rides every queued request: ``priority`` (higher
# admits first), ``tenant`` (fairness-cap key), ``deadline`` (a number
# on the caller's clock — the serving engine passes its absolute
# deadline; the pool only ever compares it, None sorting last),
# ``seq`` (arrival order, the FIFO tie-break); ``sampling`` is the
# resolved _SamplingConfig and ``adapter`` the request's LoRA bank row
_Request = collections.namedtuple(
    "_Request", ["rid", "ids", "max_new_tokens", "priority", "tenant",
                 "deadline", "seq", "sampling", "adapter"],
    defaults=(0, None, None, 0, None, 0))


class _SlotState:
    """One actively-decoding slot.  ``ids`` (the prompt) is retained so
    preemption can spill and resume without the serving layer's help:
    the cache index to restore is ``len(ids) + len(tokens) - 1``, and
    the speculative pool's draft twin re-prefills from it.
    ``sampling``/``adapter`` are the request's as-data config.
    ``ahead`` counts the tokens dispatched for this row that the host
    has not committed yet (the prefill's first token, one a step in
    flight): the host runs a step behind the device, so the row's next
    draw counter is ``sampling.draws + len(tokens) + ahead`` (the
    prefill draw was step ``draws``) and no separate step mirror is
    kept."""

    __slots__ = ("rid", "ids", "tokens", "remaining", "priority",
                 "tenant", "deadline", "seq", "sampling", "adapter",
                 "ahead")

    def __init__(self, rid, ids, tokens, remaining: int,
                 priority: int = 0, tenant=None, deadline=None,
                 seq: int = 0, sampling=None, adapter: int = 0):
        self.rid = rid
        self.ids = ids
        self.tokens = tokens
        self.remaining = remaining
        self.priority = priority
        self.tenant = tenant
        self.deadline = deadline
        self.seq = seq
        self.sampling = sampling
        self.adapter = adapter
        self.ahead = 0


class _PrefillState:
    """A slot admitted under chunked prefill whose prompt is still being
    processed: ``pos`` is the next absolute position to run (the shared
    prefix, if any, was mapped at admission and is never re-run).
    ``indexed``/``chain_key`` track incremental prefix indexing: full
    blocks enter the index AS CHUNKS COMPLETE THEM (a full block is
    immutable the moment its last position is written), so a hot prefix
    is shareable while its first owner is still prefilling the tail."""

    __slots__ = ("rid", "ids", "pos", "max_new_tokens", "indexed",
                 "chain_key", "priority", "tenant", "deadline", "seq",
                 "sampling", "adapter")

    def __init__(self, rid, ids, pos: int, max_new_tokens: int,
                 matched_blocks: int = 0, chain_key=None,
                 priority: int = 0, tenant=None, deadline=None,
                 seq: int = 0, sampling=None, adapter: int = 0):
        self.rid = rid
        self.ids = ids
        self.pos = pos
        self.max_new_tokens = max_new_tokens
        # matched blocks are already in the index; indexing resumes
        # after them, continuing their hash chain
        self.indexed = matched_blocks
        self.chain_key = chain_key
        self.priority = priority
        self.tenant = tenant
        self.deadline = deadline
        self.seq = seq
        self.sampling = sampling
        self.adapter = adapter


class _SpillState:
    """One preempted request parked in the host-RAM spill tier.

    ``host`` holds the victim's WRITTEN blocks' K/V (one numpy array
    per layer per field, ``[written, ...block shape]`` — int8 caches
    carry their fp32 scales too); ``dev_blocks[j]`` is the physical
    device block that still holds block ``j``'s content (a spilled
    block stays device-resident until the allocator actually needs it
    — resume then re-maps it with ZERO copy), or None once reclaimed
    or when block ``j`` was prefix-shared at preempt time (the host
    copy is then the only restorable source).  ``total_blocks`` is the
    admission-time reservation span, re-acquired in full at resume so
    a resumed request keeps the no-preemption-mid-decode invariant."""

    __slots__ = ("rid", "ids", "tokens", "remaining", "priority",
                 "tenant", "deadline", "seq", "total_blocks", "written",
                 "dev_blocks", "host", "host_bytes", "preempts", "shard",
                 "host_path", "sampling", "adapter")

    def __init__(self, st: "_SlotState", total_blocks: int,
                 written: int, host, host_bytes: int, shard: int = 0):
        self.rid = st.rid
        self.ids = st.ids
        self.tokens = st.tokens
        self.remaining = st.remaining
        self.priority = st.priority
        self.tenant = st.tenant
        self.deadline = st.deadline
        self.seq = st.seq
        # the as-data config rides the spill (docs §5q): resume — local
        # or on a SECOND engine via the PTKV transfer file — continues
        # the victim's own sampling stream byte-identically
        self.sampling = st.sampling
        self.adapter = st.adapter
        self.total_blocks = total_blocks
        self.written = written
        self.dev_blocks = [None] * written
        self.host = host
        self.host_bytes = host_bytes
        # the disk tier (spill_tier="disk", docs §5m): ``host`` is None
        # and ``host_path`` names the .npz holding the written blocks'
        # K/V — re-read at resume (or by a SECOND engine's restore,
        # which is the cross-engine-migration point of the tier)
        self.host_path = None
        self.preempts = 1
        # the dp shard the victim decoded in: its spilled device blocks
        # live in that shard's partition, and resume is shard-pinned —
        # a re-mapped block must stay in the partition the slot's table
        # row is sharded with (0 when dp == 1)
        self.shard = shard


class _PrefixEntry:
    """One prefix-index chain link.  ``tokens`` (the exact ids the
    block covers) guards against hash collisions: a colliding key must
    compare token-equal before its K/V are shared — a false match would
    silently serve another prompt's cache.  ``blocks`` lists EVERY
    resident physical block holding this content (identical prompts
    that prefilled concurrently each compute their own copy — the K/V
    are bit-identical, so any of them is shareable); a block leaves the
    list when its refcount hits 0, and the entry dies with its last
    block."""

    __slots__ = ("blocks", "tokens", "parent_key")

    def __init__(self, block: int, tokens: tuple, parent_key):
        self.blocks = [block]
        self.tokens = tokens
        self.parent_key = parent_key


class GenerationPool:
    """Continuous batching: submit prompts, drain one decode step at a
    time, collect per-request token arrays.

    ``model`` is a live cached-decode model (``models.TransformerLM``);
    the artifact-serving ``Predictor`` stays a fixed-program runner —
    generation needs the cache-threaded forward, so the pool owns the
    model directly (see docs/DESIGN.md, prefill/decode split).
    """

    def __init__(self, model, max_len: int, slots: int = 4,
                 buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_id: Optional[int] = None,
                 cache_dtype="float32", donate: Optional[bool] = None,
                 seed: int = 0, cache_layout: str = "dense",
                 block_size: int = 32, num_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefix_sharing: bool = False,
                 tenant_slot_cap: Optional[int] = None,
                 mesh: Optional[DecodeMesh] = None,
                 route: str = "auto", spill_tier: str = "host",
                 spill_dir: Optional[str] = None,
                 prefill_only: bool = False,
                 collective_quant: Optional[str] = None,
                 collective_quant_scale: Optional[str] = None):
        if slots < 1:
            raise InvalidArgumentError("GenerationPool needs slots >= 1")
        if mesh is not None and not isinstance(mesh, DecodeMesh):
            raise InvalidArgumentError(
                "mesh must be a jit.mesh.DecodeMesh (or None for the "
                "unsharded pool), got %r" % (type(mesh).__name__,))
        self._mesh = mesh
        self._dp = 1 if mesh is None else mesh.dp
        if slots % self._dp != 0:
            raise InvalidArgumentError(
                "dp=%d must divide slots=%d: the slot axis is sharded "
                "in equal contiguous chunks over the dp mesh axis, and "
                "the allocator maps logical slot g to (shard g // "
                "(slots/dp), local slot g %% (slots/dp))"
                % (self._dp, slots))
        self._slots_per_shard = int(slots) // self._dp
        if tenant_slot_cap is not None and int(tenant_slot_cap) < 1:
            raise InvalidArgumentError(
                "tenant_slot_cap must be >= 1 slots per tenant (or None "
                "for no fairness cap), got %r" % (tenant_slot_cap,))
        # the string is validated FIRST (jit.cache registry — typed
        # error naming the registry for an unknown one)
        get_layout(cache_layout)
        if prefill_chunk_tokens is not None \
                and int(prefill_chunk_tokens) < 1:
            raise InvalidArgumentError(
                "prefill_chunk_tokens must be >= 1 tokens of prompt "
                "work per tick, got %r" % (prefill_chunk_tokens,))
        # the session owns the model binding, the sampling config and the
        # bucketed batch-1 prefill; the pool adds the slot-batched layer.
        # The session shares the pool's cache layout so a paged pool gets
        # paged (identity-tabled, batch-1) row caches from prefill whose
        # blocks splice straight into the pool's global block pool.
        # the route rides the session (validated there) and is ambient
        # for every traced body that goes through _run_model — the
        # pool's batched decode step, the chunk prefill, and the
        # speculative subclass's draft/verify included (§5l)
        # the mp-collective quant mode rides the session (validated
        # there, defaulting to the mesh's) and is ambient for the
        # DECODE traced bodies only — this pool's slot-batched step
        # included; prefill/chunk bodies stay dense (docs §5r)
        self._session = DecodeSession(
            model, max_len, buckets=buckets, temperature=temperature,
            top_k=top_k, top_p=top_p, cache_dtype=cache_dtype,
            donate=donate, cache_layout=cache_layout,
            block_size=block_size, mesh=mesh, route=route,
            collective_quant=collective_quant,
            collective_quant_scale=collective_quant_scale)
        # the layout every guard below and every hook dispatches on is
        # what the model's cache ENTRIES are (the session derived it:
        # jit.cache.layout_of), so they ask capabilities and never
        # compare strings, and a layout that cannot address positions
        # combined with a positional-only knob fails HERE naming the
        # layers — never a silent no-op faking hit rates downstream.
        # ``cache_layout`` (public) is the layout's name: the caller's
        # string for a model with one kind of entry, derived
        # ('paged+recurrent') for a model that mixes kinds
        self._layout = self._session._layout
        self._kv_layout = cache_layout
        cache_layout = self._layout.name
        self._check_positional_knobs(prefill_chunk_tokens, prefix_sharing)
        self._model = model
        self._cache_dtype = cache_dtype
        from ..jit.speculative import model_vocab_size
        self._vocab = model_vocab_size(model)
        # LoRA bank GEOMETRY (nn.lora; (n_adapters, rank) or None):
        # shapes are compiled into the executables and fingerprinted;
        # bank CONTENTS are hot-swappable weights (load_adapter)
        self._lora_cfg = _lora_mod.lora_config(model)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.cache_layout = cache_layout
        self._block_size = int(block_size)
        # paged: ceil so a ragged final block still holds max_len
        self._max_blocks = -(-self.max_len // self._block_size)
        if self._layout.paged:
            # physical block s*(num_blocks/dp) is shard s's reserved
            # SCRATCH block — that shard's unmapped table entries point
            # at it, its inactive-slot writes land in it (with dp=1
            # this is the familiar global block 0); default pool size
            # is FULL capacity (every slot at max_len); a smaller
            # num_blocks is the point of paging: HBM scales with the
            # token budget, and admission control (below) defers
            # refills that couldn't finish within the remaining blocks.
            # Under a mesh the block pool's leading axis is sharded
            # over dp in equal contiguous chunks, so the allocator runs
            # ONE FREE LIST PER SHARD — a slot's blocks always live in
            # its own shard's partition of the pool array, and the
            # decode step never gathers K/V across the dp axis
            if num_blocks is None:
                num_blocks = self._dp * (
                    1 + self._slots_per_shard * self._max_blocks)
            num_blocks = int(num_blocks)
            if num_blocks % self._dp != 0:
                raise InvalidArgumentError(
                    "dp=%d must divide num_blocks=%d: the block pool is "
                    "partitioned into equal per-shard spans (each with "
                    "its own scratch block and free list)"
                    % (self._dp, num_blocks))
            if num_blocks // self._dp < 2:
                raise InvalidArgumentError(
                    "paged pool needs >= 2 blocks per dp shard (one "
                    "scratch + one allocatable), got num_blocks=%d at "
                    "dp=%d" % (num_blocks, self._dp))
            self._num_blocks = num_blocks
            self._blocks_per_shard = num_blocks // self._dp
            self._free_by_shard: List[List[int]] = [
                list(range(s * self._blocks_per_shard + 1,
                           (s + 1) * self._blocks_per_shard))
                for s in range(self._dp)]
            self._slot_blocks: Dict[int, List[int]] = {}
            # refcount per RESIDENT physical block (absent = free).  A
            # freshly allocated block starts at 1; prefix sharing bumps
            # it per additional table row mapping the block; release/
            # finish/cancel DECREF, and only refcount 0 returns a block
            # to _free_blocks — so a block can never be freed out from
            # under another slot's table row
            self._block_refs: Dict[int, int] = {}
        elif num_blocks is not None:
            raise InvalidArgumentError(
                "num_blocks is a paged-cache knob; pass "
                "cache_layout='paged' (got %r)" % (cache_layout,))
        # per-slot scratch routing: slot g's masked/ unmapped table
        # entries point at ITS shard's scratch block (all zeros when
        # dp == 1 — exactly the legacy global scratch).  A plain numpy
        # constant: the traced step closes over it, and it never
        # changes after construction
        self._scratch_row = np.asarray(
            [self._shard_scratch(self._shard_of_slot(g))
             for g in range(self.slots)], np.int32) \
            if self._layout.paged else None
        self._cache = self._new_cache()
        # entries and bytes a slot, by the entries' kind; what a step
        # reads and writes of recurrent state is the recurrent entries'
        self._by_kind = self._layout.bytes_per_slot_by_kind(
            self._cache, self.slots, self.max_len)
        # K/V planes an entry holds: one a pass for a model that runs its
        # stack several times (``models.LoopedLM.cache_planes``), side by
        # side on the entry's head axis, so every figure by block or by
        # slot above has them in it already; what is counted apart is
        # how many there are, and the passes a step makes
        self._planes = int(getattr(model, "cache_planes", 1))
        self.loop_passes = 0
        # what a paged pool's block counts run over (``tick.decode``'s
        # meta): the entries behind a block table, the K/V planes they
        # hold in all, the passes where an entry holds more than one
        paged = sum(n for k, (n, _) in self._by_kind.items()
                    if k not in ("recurrent", "window"))
        self._entries_meta = dict(kv_entries=paged,
                                  kv_planes=paged * self._planes)
        if self._planes > 1:
            self._entries_meta["passes"] = self._planes
        # window entries (``jit.cache.WindowLayout``): a ring of blocks a
        # slot, in pools of their own that no allocator maps; the block
        # counts above are then the OTHER paged entries' alone.  ``_ring``
        # is ``(window, ring blocks)``, None for a model with no such
        # entry; ``window_blocks_overwritten`` counts, from positions,
        # the ring entries a slot's position has lapped (once a slot,
        # whatever the number of window entries, as ``live_blocks`` is)
        rings = self._layout.entries(self._cache, "window")
        self._ring = (int(rings[0].window), int(rings[0].table.shape[1])) \
            if rings else None
        self.window_blocks_overwritten = 0
        if rings:
            self._entries_meta.update(window_entries=len(rings),
                                      window=self._ring[0],
                                      ring_blocks=self._ring[1])
        # how a step's new K/V rows reach their pools (one in-place
        # kernel a layer, or a scatter a pool), from shapes, types and
        # the session's route, as the step's trace decides it; an entry
        # of latents has no K/V pool and says nothing
        with decode_route(self._session.route):
            writes = {paged_kv_write_route(entry.k, self._rows_a_slot)
                      for entry in self._cache
                      if hasattr(entry, "table") and hasattr(entry, "k")}
        if writes:
            self._entries_meta["kv_write"] = "+".join(sorted(writes))
        self._state_bytes_slot = self._by_kind.get("recurrent", (0, 0))[1]
        # a window entry's ring is a constant of the slot too
        self._window_bytes_slot = self._by_kind.get("window", (0, 0))[1]
        # the same, as ``cache_stats()`` hands it out every tick
        self._by_kind_stats = {
            "bytes_per_slot": {k: b for k, (_, b) in self._by_kind.items()},
            "cache_entries": {k: n for k, (n, _) in self._by_kind.items()}}
        if self._planes > 1:
            self._by_kind_stats.update(
                passes=self._planes,
                cache_planes=self._entries_meta["kv_planes"])
        # the model's routed expert layers as ``(held, experts, top_k)``,
        # and the route the step's rows compile them to: from shapes
        experts = [layer for layer in model.sublayers()
                   if isinstance(layer, SparseExperts)]
        self._experts = [(layer.held[1], layer.num_experts, layer.top_k)
                         for layer in experts]
        self._experts_held = sum(held for held, _, _ in self._experts)
        self._expert_route = "+".join(sorted(
            {layer.route_at(self.slots * self._rows_a_slot)
             for layer in experts}))
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._decode_jit = jax.jit(self._pool_decode,
                                   donate_argnums=(2,) if donate else ())
        # donate the POOL cache (argnum 0) to the insert too: the splice
        # is in-place
        self._insert_jit = jax.jit(self._insert,
                                   donate_argnums=(0,) if donate else ())
        # compilation routes through the AOT path (jit.aot) so the
        # pool's executables carry the compiler's own cost/memory
        # attribution (cost_report()).  Shapes are pool-fixed — the
        # token vector keys the one batched decode executable, the
        # insert's slot/length/table args are traced scalars/vectors —
        # so each wrapper holds exactly the executables the
        # compile-count contract already pins
        self._decode_jit = aot.AotFunction(
            self._decode_jit,
            key_fn=lambda p, b, cache, toks, *r: aot.shape_key(toks),
            name="pool_decode",
            meta_fn=lambda p, b, cache, *r: {
                "kv_cache_bytes": aot.kv_arg_bytes(cache)})
        self._insert_jit = aot.AotFunction(
            self._insert_jit,
            key_fn=lambda pool_cache, row_cache, *r: "slot_insert",
            name="slot_insert")
        # chunked prefill + prefix sharing (paged only; docs §5i).  The
        # executables exist only when the knob is on, so a plain pool's
        # compile_counts()/cost_report() keys are exactly the pinned
        # pre-existing set
        self._chunk_tokens = (None if prefill_chunk_tokens is None
                              else int(prefill_chunk_tokens))
        self.prefix_sharing = bool(prefix_sharing)
        self._prefilling: Dict[int, _PrefillState] = {}
        self._chunk_jit = None
        self._admit_jit = None
        if self._chunk_tokens is not None:
            dn = (2,) if donate else ()
            self._chunk_jit = aot.AotFunction(
                jax.jit(self._prefill_chunk, donate_argnums=dn),
                key_fn=lambda p, b, cache, toks, *r: aot.shape_key(toks),
                name="prefill_chunk",
                meta_fn=lambda p, b, cache, *r: {
                    "kv_cache_bytes": aot.kv_arg_bytes(cache)})
            self._admit_jit = aot.AotFunction(
                jax.jit(self._admit, donate_argnums=(0,) if donate
                        else ()),
                key_fn=lambda *a: "slot_admit", name="slot_admit")
        # prefix index: chain-hash key -> resident full block (entries
        # removed the moment their block's refcount hits 0), plus the
        # reverse map used for that removal.  Hit accounting is
        # cumulative (the serving gauges read it)
        self._prefix_index: Dict[int, _PrefixEntry] = {}
        self._block_keys: Dict[int, int] = {}
        # head-of-queue match memo: a blocked FIFO head would otherwise
        # re-walk its whole prefix chain (tuple-build + hash per block)
        # EVERY tick until blocks free.  The epoch bumps on any
        # allocator/index mutation, so a memoized match is exactly as
        # fresh as a recomputed one
        self._prefix_epoch = 0
        self._head_match = None
        # the allocator's version (``alloc_version``): every slot take
        # and release and every block allocation, free, share, spill and
        # resume bumps it, so whoever reads ``cache_stats()`` a tick can
        # tell by one int compare that it would read what it read
        self._alloc_version = 0
        self._prefix_queries = 0
        self._prefix_hits = 0
        self._prefix_tokens_matched = 0
        self._prefix_blocks_matched = 0
        self._chunks_total = 0
        self._chunk_tokens_total = 0
        # the engine's _on_admit reads this right after the pool fires
        # on_admit (same synchronous call chain): matched prefix tokens
        # of the LAST admission, None when sharing is off
        self.last_admit_prefix_tokens: Optional[int] = None
        # sampling is PER-REQUEST DATA (docs §5q): the constructor's
        # temperature/top_k/top_p are only the DEFAULTS submit() applies
        # when a request names none, and ``seed`` seeds the default
        # per-request stream assignment (request seed = seed + seq).
        # Nothing here is compiled in, so the config fingerprint no
        # longer carries any of it — a journal/transfer peer with
        # different defaults replays byte-identically, because every
        # record carries its own resolved config.
        self._sampling_seed = int(seed)
        self._queue: collections.deque = collections.deque()
        self._active: Dict[int, _SlotState] = {}
        self._free: List[int] = list(range(self.slots))
        # traffic-grade scheduling state (docs §5j): the per-tenant
        # fairness cap, the arrival counter behind the FIFO tie-break,
        # and the host-RAM spill tier — preempted requests parked with
        # their K/V host copies, plus the reverse map from a still-
        # device-resident spilled block to its owner (the allocator
        # reclaims through it under pressure).  ``admission_blocked``
        # is refreshed by every _refill: True when the chosen candidate
        # could not reserve its blocks — the serving engine's
        # degradation ladder reads it to decide preemption is worth it
        self._tenant_cap = (None if tenant_slot_cap is None
                            else int(tenant_slot_cap))
        # spill tier backend (docs §5m): "host" parks preempted K/V in
        # process RAM (the §5j tier — dies with the process); "disk"
        # writes each victim's blocks to <spill_dir>/<rid>.npz so the
        # parked state survives a crash and a SECOND engine can adopt
        # it at restore.  The allocator partition and the resume paths
        # are identical either way — only where the host copy lives
        # changes.
        if spill_tier not in ("host", "disk"):
            raise InvalidArgumentError(
                "spill_tier must be 'host' (process-RAM, dies with the "
                "engine) or 'disk' (crash-durable .npz files under "
                "spill_dir), got %r" % (spill_tier,))
        if spill_tier == "disk" and self._planes > 1:
            raise InvalidArgumentError(
                "spill_tier='disk' writes K/V blocks by head to a PTKV "
                "file, whose header states one plane of heads a layer; "
                "this model's cache entries hold %d K/V planes (one a "
                "pass): keep spill_tier='host', which carries a block's "
                "planes together in memory" % self._planes)
        if spill_tier == "disk":
            if self._layout.windowed:
                raise InvalidArgumentError(
                    "spill_tier='disk' writes K/V blocks by head to a PTKV "
                    "file from the allocator's block lists; "
                    "cache_layout=%r %s — no tier carries a window entry "
                    "yet" % (cache_layout, self._layout.not_transferable()))
            if not self._layout.spillable:
                raise InvalidArgumentError(
                    "spill_tier='disk' spills per-slot decode state "
                    "(paged K/V blocks, or a recurrent state carry); a "
                    "dense pool has no spill granularity — pass "
                    "cache_layout='paged' or 'recurrent'")
            if not self._layout.transferable:
                raise InvalidArgumentError(
                    "spill_tier='disk' writes one kind of cache entry to "
                    "a PTKV file (K/V blocks by head, or state rows); "
                    "cache_layout=%r %s — keep spill_tier='host', which "
                    "carries it in memory"
                    % (cache_layout, self._layout.not_transferable()))
            if spill_dir is None:
                raise InvalidArgumentError(
                    "spill_tier='disk' needs spill_dir= (the directory "
                    "the per-request .npz spill files live in; a second "
                    "engine restores from the same directory)")
            os.makedirs(spill_dir, exist_ok=True)
        elif spill_dir is not None:
            raise InvalidArgumentError(
                "spill_dir is a spill_tier='disk' knob (got spill_dir "
                "with spill_tier=%r)" % (spill_tier,))
        self.spill_tier = spill_tier
        self._spill_dir = None if spill_dir is None else str(spill_dir)
        # prefill tier mode (docs §5n): the pool runs admission +
        # prefill as usual, but a request that survives its first token
        # PARKS instead of decoding — export_kv() then hands its
        # written blocks + committed state to a decode-tier pool over
        # the K/V transfer contract.  Requires the disk spill tier (the
        # export writer IS the spill writer) and therefore paged.
        if prefill_only and spill_tier != "disk":
            raise InvalidArgumentError(
                "prefill_only=True exports finished prefills over the "
                "K/V transfer contract, which lives in the disk spill "
                "tier — pass spill_tier='disk' (and spill_dir=)")
        if prefill_only and self._layout.recurrent:
            raise InvalidArgumentError(
                "prefill_only=True (the disaggregated prefill tier) is "
                "not wired for cache_layout=%r (%s): a recurrent "
                "prefill is one cheap O(L·d_state) scan, so there is "
                "nothing to disaggregate — run a fused engine"
                % (cache_layout, self._layout.recurrent_entries()))
        self._prefill_only = bool(prefill_only)
        # rid -> (slot, _SlotState) for prefill-complete parked
        # requests awaiting export_kv()
        self._prefill_done: Dict[object, tuple] = {}
        # serving-layer hook: on_prefill_done(rid) the moment a
        # prefill-only request parks (fires inside step(), after the
        # first token's on_token)
        self.on_prefill_done = None
        self._seq = 0
        self._spilled: Dict[object, _SpillState] = {}
        self._spill_owner: Dict[int, tuple] = {}
        self.admission_blocked = False
        self._preempts_total = 0
        self._resumes_total = 0
        self._spill_bytes_total = 0
        self._upload_bytes_total = 0
        self._spill_reclaims_total = 0
        # serving-layer hook: on_resume(rid, info) after a preempted
        # request's slot is re-activated (fires inside _refill, like
        # on_admit)
        self.on_resume = None
        # the step's CARRY lives on the device (docs §5t): the decoded
        # token vector and the per-row draw counter feed straight back
        # from one step into the next, and a row that joins takes its
        # values by a device-side select (``_patch_carry``), never from
        # a host mirror — with a step in flight the host's copy would be
        # a step stale.  What the host knows without the device — which
        # rows are live, their sampling config and adapter ids (docs
        # §5q) — is uploaded whole whenever ``_live_sig``, the (slot,
        # arrival) pairs of the rows a launch takes, changes
        self._tok_dev = None
        self._step_dev = None
        self._active_dev = None
        self._samp_dev = None
        self._adapter_dev = None
        self._live_sig = None
        # whether a row of the uploaded config draws (temperature > 0):
        # what the step's sampler branches on, known here without the
        # device; ``steps_drawing`` counts the launches that held one
        self._draws = False
        self.steps_drawing = 0
        carry_to = {} if mesh is None else {
            "out_shardings": (mesh.sharding("dp"), mesh.sharding("dp"))}
        self._patch_jit = jax.jit(self._patch, **carry_to)
        # the host runs one step behind the device: ``_flights`` holds
        # the steps launched and not yet downloaded, oldest first, each
        # ``(handles, rows)`` with ``rows`` the ``(slot, state)`` pairs
        # the step was launched for; ``_firsts`` the prefills' first
        # tokens still on the device, ``(slot, state, token)``: they
        # ride the next download.  ``_rows`` is the rows of the step the
        # running hook is about
        self._flights: collections.deque = collections.deque()
        self._firsts: List[tuple] = []
        self._rows: Sequence[tuple] = ()
        self._results: Dict[object, np.ndarray] = {}
        self._finish_reasons: Dict[object, str] = {}
        # serving-layer lifecycle hooks (paddle_tpu.serving sets these):
        # on_admit(rid, slot, prompt_len) when a queued request takes a
        # slot; on_tokens(batch) ONCE for the tokens a download
        # delivered, the prefills' first tokens among them, ``batch``
        # the ``(rid, token, commit_step)`` triples in the order they
        # were committed (a request's in its own order, 0..n of them;
        # ``commit_step`` is the block pool's, else None);
        # on_finish(rid, tokens, reason) after that call, when a request
        # completes (NOT on cancel/release — aborting is the caller's
        # act, not a completion).  Hooks fire inside step(), so the
        # timings they record come from the real code path.  A pool used
        # alone may set on_token(rid, token) instead: the batch hook a
        # pool starts with hands it every token in turn.
        self.on_admit = None
        self.on_token = None
        self.on_tokens = self._each_token
        self.on_finish = None
        # the tokens committed and not yet handed on, and the slots
        # whose request ended on one of them (``_hand_on``); calls into
        # the token hooks since the deliver phase began
        self._out: List[tuple] = []
        self._ending: List[int] = []
        self._hook_calls = 0
        # ids currently queued/active/uncollected, maintained
        # incrementally so submit stays O(1) in a long-lived pool
        self._used_rids: set = set()
        self._next_rid = 0
        # parameter/buffer value lists are rebuilt lazily, not per token:
        # the per-step python cost of walking a deep model's parameters
        # would sit on the decode hot path
        self._state_cache = None

    def _check_positional_knobs(self, prefill_chunk_tokens,
                                prefix_sharing) -> None:
        """Chunked prefill and prefix sharing write and share through a
        block table over EVERY layer's positions: typed errors for a
        layout without one, naming the layers that keep a recurrent
        state where that is why."""
        lay = self._layout
        if lay.windowed and (prefill_chunk_tokens is not None
                             or prefix_sharing):
            raise InvalidArgumentError(
                "%s cannot apply to cache_layout=%r: a window entry keeps "
                "a slot's last positions on a ring of blocks of its own%s"
                % (("prefix_sharing", lay.name,
                    ", so the blocks of a shared prefix that lie behind "
                    "the window do not exist to be shared (a ring drawn "
                    "from the allocator, and sharing that knows which "
                    "layers share, are not built)")
                   if prefix_sharing else
                   ("prefill_chunk_tokens", lay.name,
                    ", and a prompt's chunk that starts mid-way may ask "
                    "for keys the ring has already overwritten: a prompt "
                    "is prefilled whole, in one bucket")))
        if lay.prompt_from_zero and (prefill_chunk_tokens is not None
                                     or prefix_sharing):
            raise InvalidArgumentError(
                "%s cannot apply to cache_layout=%r: a latent cache entry "
                "runs the expanded form of latent attention (a prompt "
                "over its own keys) only for a chunk that starts at "
                "position 0; one that starts mid-way runs absorbed "
                "through the XLA composition, whose float32 scores are "
                "[rows, heads, chunk, context], and no kernel takes a "
                "prompt-sized chunk against cached latents yet"
                % ("prefill_chunk_tokens"
                   if prefill_chunk_tokens is not None
                   else "prefix_sharing", lay.name))
        if prefill_chunk_tokens is not None \
                and not (lay.paged and lay.positional):
            # the chunk path writes through the block table (per-slot
            # scatter routed to the scratch block past the reservation);
            # the dense layout keeps its one-shot bucketed prefill, so
            # dense pools are byte-for-byte unaffected by this feature
            if not lay.positional:
                raise InvalidArgumentError(
                    "prefill_chunk_tokens cannot apply to cache_layout="
                    "%r (%s): a recurrence has no positional K/V to "
                    "chunk into — its whole prefill is one O(L·d_state) "
                    "scan, already cheap enough to run in-tick"
                    % (lay.name, lay.recurrent_entries()))
            raise InvalidArgumentError(
                "prefill_chunk_tokens is a paged-cache knob (chunk "
                "writes route through the block table); pass "
                "cache_layout='paged' (got %r)" % (lay.name,))
        if prefix_sharing and not (lay.paged and lay.positional):
            if not lay.positional:
                raise InvalidArgumentError(
                    "prefix_sharing cannot apply to cache_layout="
                    "%r (%s): the recurrence folds the whole prefix "
                    "into one carry, so there are no per-position "
                    "blocks two requests could share — every request's "
                    "state is already O(1)"
                    % (lay.name, lay.recurrent_entries()))
            raise InvalidArgumentError(
                "prefix_sharing shares physical KV blocks through the "
                "block table; pass cache_layout='paged' (got %r)"
                % (lay.name,))
        if prefix_sharing and prefill_chunk_tokens is None:
            # the win of a prefix hit is skipping straight to the
            # unmatched suffix, and ONLY the chunk executable can start
            # a prompt mid-way (bucketed prefill always runs from token
            # 0, which would recompute the shared prefix it just
            # mapped) — so sharing without chunking is a misconfig, not
            # a degraded mode
            raise InvalidArgumentError(
                "prefix_sharing needs prefill_chunk_tokens: admission "
                "skips the matched prefix and chunk-prefills only the "
                "suffix — pass prefill_chunk_tokens=<tokens per tick> "
                "(e.g. the block size or a small multiple)")

    # -- traced bodies ---------------------------------------------------
    def _insert(self, pool_cache, row_cache, slot, length, blocks=None):
        """Splice a batch-1 prefilled row cache into ``slot``; the slot
        id, true length and (paged) block ids are traced, so every refill
        reuses one compilation.

        The splice body is the layout's (``jit.cache.CacheLayout
        .insert_row`` — the paged scatter through ``blocks``, the dense
        per-slot set, the recurrent state-carry copy); this wrapper
        owns the jit/donation plumbing around it.
        """
        return self._layout.insert_row(pool_cache, row_cache, slot,
                                       length, blocks)

    @staticmethod
    def _patch(toks, steps, slot, tok, step):
        """A row joins the step's carry ON THE DEVICE: the token its
        next step consumes (a prefill's own output array, or a resumed
        request's last token) and its draw counter, at ``slot``; every
        other row keeps what the step in flight computes for it."""
        return (toks.at[slot].set(jnp.reshape(tok, ()).astype(toks.dtype)),
                steps.at[slot].set(step))

    def _pool_decode(self, param_vals, buf_vals, cache, toks, active,
                     samp, step, adapter):
        """One batched decode step over every slot; inactive slots are
        frozen (their cache index does not advance, their token output is
        forced to 0) so a free slot can never creep past max_len.

        ``samp`` (the (temperature, top_k, top_p, seed) [slots] vectors),
        ``step`` (per-row draw counters) and ``adapter`` (per-row LoRA
        ids) are DATA riding the step (docs §5q): every slot samples
        under its own config and gathers its own adapter rows inside the
        ONE compiled executable.  ``step`` advances only for active rows
        and is returned to feed back on-device.

        Paged: an inactive slot's table row is zeroed FOR THE STEP so
        its (discarded) write lands in the scratch block — its old blocks
        may already belong to a refilled request, and a stale-table write
        would corrupt that request's cache — and its index reads 0, so
        the kernel walks one block of scratch for it and not its last
        request's length.  The ORIGINAL rows and index are restored in
        the returned cache: under chunked prefill an inactive slot can
        be mid-prompt, and persisting the masked row would wipe the
        mapping its next chunk writes through and the position it
        writes at."""
        sess = self._session
        given = cache
        # each entry by its own layout: a paged entry's table and index
        # masked, a recurrent entry's update window closed on the free
        # slots (jit.cache), whichever kinds this model's layers keep
        cache = self._layout.begin_step(self._masked_tables(cache, active),
                                        active)
        logits, new_cache = sess._run_model(param_vals, buf_vals,
                                            toks[:, None], cache,
                                            adapter,
                                            collective_seam=True)
        temp, tk, tp, seed = samp
        # scopes by hand where no Layer runs: they name these
        # operations in a device profile as the module tree names the
        # model's (nn.Layer.__call__)
        with jax.named_scope("sample"):
            tok = sample_logits_data(logits[:, 0], temp, tk, tp, seed,
                                     step)
        step = step + active.astype(step.dtype)
        # layout-owned freeze (jit.cache): positional layouts merge the
        # index; the recurrent layout also re-opens the update window
        # that ``begin_step`` closed on the inactive slots (their carry
        # came through the step as identity steps, so nothing of the
        # state's size is selected here)
        with jax.named_scope("cache_freeze"):
            new_cache = self._layout.freeze_step(new_cache, given, active)
        new_cache = [c._replace(table=g.table) if lay.paged else c
                     for lay, c, g in zip(self._layout.layouts(given),
                                          new_cache, given)]
        return new_cache, jnp.where(active, tok, 0), step

    def _masked_tables(self, cache, active):
        """Inactive slots' table rows routed to their OWN shard's
        scratch block for the step (all zeros when dp == 1 — the
        legacy global scratch): a stale write may not land in blocks a
        refilled request now owns, and under a mesh it may not cross
        into another shard's partition either.  Their index reads 0 for
        the step: the attention kernel's cost follows the positions a
        row says it holds, and a free slot's index is its last
        request's length.  The caller restores both from the cache it
        was given.  Entries that are not paged pass through.  Traced
        helper, shared with the speculative verify step and the
        block-diffusion step."""
        if not self._layout.paged:
            return cache
        scratch = jnp.asarray(self._scratch_row)[:, None]
        return [c._replace(table=jnp.where(active[:, None], c.table,
                                           scratch),
                           index=jnp.where(active, c.index, 0))
                if lay.paged else c
                for lay, c in zip(self._layout.layouts(cache), cache)]

    def _admit(self, cache, slot, row, index):
        """Map an admitted request's table row (shared prefix blocks +
        freshly allocated suffix blocks, scratch-padded) and set its
        cache index to the matched prefix length — the chunked-prefill
        admission write.  No K/V move: the shared blocks are already
        resident and the suffix is computed by later chunk calls."""
        return [c._replace(table=c.table.at[slot].set(row),
                           index=c.index.at[slot].set(
                               jnp.asarray(index, jnp.int32)))
                for c in cache]

    def _prefill_chunk(self, param_vals, buf_vals, cache, toks, slot,
                       start, length, samp, adapter):
        """One fixed-shape prompt chunk for ONE slot: run ``toks`` (a
        ``[C]`` vector holding ``length`` real tokens, zero-padded at
        the back to the fixed C) from
        absolute position ``start`` through the slot's table row, and
        sample the token at offset ``length - 1`` (only the final
        chunk's sample — the request's FIRST token — is ever used;
        ``samp`` is the request's (temperature, top_k, top_p, seed,
        step) [1] vectors with step fixed at the submission's stream
        offset, so intermediate chunks' discarded samples cost nothing
        and the kept one matches the bucketed path exactly).

        The forward is a batch-1 view over the GLOBAL block pools: the
        slot's table row is sliced out, so writes scatter into the same
        physical blocks the batched decode step reads, through the same
        per-slot addressing (positions past the table span land in the
        scratch block).  Pad positions write garbage into the request's
        OWN future positions — masked until real tokens overwrite them,
        exactly the bucketed prefill's pad discipline — and can never
        touch a SHARED block: shared blocks end before ``start``, and
        every written position is >= start."""
        sess = self._session
        views = [c._replace(
            table=jax.lax.dynamic_slice(
                c.table, (slot, 0), (1, c.table.shape[1])),
            index=jnp.full((1,), start, jnp.int32)) for c in cache]
        logits, new_views = sess._run_model(param_vals, buf_vals,
                                            toks[None], views, adapter)
        last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                            axis=0, keepdims=False)
        temp, tk, tp, seed, step = samp
        with jax.named_scope("sample"):
            tok = sample_logits_data(last[None], temp, tk, tp, seed,
                                     step)
        out = [c._replace(k=v.k, v=v.v, k_scale=v.k_scale,
                          v_scale=v.v_scale,
                          index=c.index.at[slot].set(
                              jnp.asarray(start + length, jnp.int32)))
               for c, v in zip(cache, new_views)]
        return out, tok[0]

    # -- host API --------------------------------------------------------
    def _resolve_sampling(self, temperature, top_k, top_p, seed) \
            -> _SamplingConfig:
        """Resolve a submit-edge sampling spec to a fully-concrete
        ``_SamplingConfig``: None fields take the pool's constructor
        defaults, and a None seed takes the deterministic per-request
        default ``pool_seed + seq`` (distinct streams per request,
        reproducible across runs).  The resolved record — never the
        defaults — is what rides the slot, spill, journal and PTKV
        header."""
        sess = self._session
        t = sess.temperature if temperature is None else float(temperature)
        k = sess.top_k if top_k is None else int(top_k)
        p = sess.top_p if top_p is None else float(top_p)
        check_sampling(t, p)
        if seed is None:
            seed = self._sampling_seed + self._seq
        return _SamplingConfig(t, k, p, int(seed) & 0xFFFFFFFF)

    @staticmethod
    def _resubmit_sampling(cfg: Optional[_SamplingConfig],
                           committed: int) -> _SamplingConfig:
        """The config a prompt+committed resubmission carries: same
        temperature/top-k/top-p/seed, ``draws`` advanced by the tokens
        already committed — the re-prefill's draw then lands at exactly
        the stream step the original continuation would have used, so
        even the degraded resubmit path stays byte-identical for
        SAMPLED requests, not just greedy ones."""
        if cfg is None:
            cfg = _SamplingConfig(0.0, 0, 1.0, 0)
        return cfg._replace(draws=cfg.draws + int(committed))

    def _check_adapter(self, adapter) -> int:
        """Validate a submit-edge adapter id against the attached bank
        geometry (id 0 — the base model — is always valid, bank or
        not)."""
        adapter = int(adapter)
        if adapter == 0:
            return 0
        if self._lora_cfg is None:
            raise InvalidArgumentError(
                "adapter=%d but the model has no LoRA bank attached: "
                "call nn.lora.attach_lora(model, n_adapters, rank) "
                "BEFORE constructing the pool (the bank must be in the "
                "parameter snapshot), then load_adapter" % adapter)
        n, _ = self._lora_cfg
        if not 0 <= adapter < n:
            raise InvalidArgumentError(
                "adapter id must be in [0, n_adapters=%d), got %d"
                % (n, adapter))
        return adapter

    @staticmethod
    def _samp_vec(cfg: Optional[_SamplingConfig]):
        """One resolved config as the (temperature, top_k, top_p, seed,
        step) ``[1]`` device vectors the batch-1 chunk path consumes
        (None -> greedy).  ``step`` is the config's ``draws`` offset —
        a fresh request's prefill draw is stream step 0, a
        resubmission's lands where the original stream left off."""
        if cfg is None:
            cfg = _SamplingConfig(0.0, 0, 1.0, 0)
        return (jnp.asarray([cfg.temperature], jnp.float32),
                jnp.asarray([cfg.top_k], jnp.int32),
                jnp.asarray([cfg.top_p], jnp.float32),
                jnp.asarray([cfg.seed & 0xFFFFFFFF], jnp.uint32),
                jnp.asarray([cfg.draws], jnp.uint32))

    def submit(self, input_ids, max_new_tokens: int, request_id=None,
               priority: int = 0, tenant=None, deadline=None,
               temperature=None, top_k=None, top_p=None, seed=None,
               adapter: int = 0, _sampling=None):
        """Queue one prompt (1-D ids); returns the request id.

        ``priority`` (int, higher admits first), ``tenant`` (hashable
        fairness-cap key) and ``deadline`` (a NUMBER on any consistent
        clock — the pool only compares it; earlier wins within a
        priority class, and None sorts last as infinitely lax) are
        SCHEDULING metadata consumed by ``_refill``'s candidate
        selection; all default to the strict-FIFO behavior.

        ``temperature``/``top_k``/``top_p``/``seed`` are THIS request's
        sampling config (None -> the pool's constructor defaults; the
        resolved values ride the batched step as per-slot data, so any
        mix shares the one executable — docs §5q).  ``adapter`` picks
        the request's LoRA bank row (0 = base model)."""
        if deadline is not None and (isinstance(deadline, bool)
                                     or not isinstance(deadline,
                                                       (int, float))):
            # the candidate ordering mixes deadlines with the
            # float('inf') sentinel for deadline-less requests: a
            # non-numeric "orderable" would TypeError mid-refill,
            # killing every later step — reject it at the submit edge
            raise InvalidArgumentError(
                "deadline must be a number on the caller's clock (or "
                "None for no deadline), got %r" % (deadline,))
        ids = np.asarray(getattr(input_ids, "value", input_ids))
        if ids.ndim != 1:
            raise InvalidArgumentError(
                "GenerationPool.submit takes ONE prompt (1-D ids, got "
                "shape %s); batch parallelism comes from the slots"
                % (ids.shape,))
        if len(ids) < 1:
            raise InvalidArgumentError(
                "prompt must contain at least one token")
        if self._vocab is not None and ids.size and (
                int(ids.min()) < 0 or int(ids.max()) >= self._vocab):
            # out-of-vocab ids would be silently CLAMPED by the
            # embedding gather — garbage output conditioned on the
            # wrong row; checked here (the pool owns the model) so
            # direct pool users, the engine, and the HTTP boundary all
            # fail fast with the same typed error
            raise InvalidArgumentError(
                "prompt token ids must be in [0, vocab_size=%d): "
                "got range [%d, %d] — out-of-vocab ids would be "
                "clamped to the wrong embedding row, not rejected "
                "by the model" % (self._vocab, int(ids.min()),
                                  int(ids.max())))
        if len(ids) + max_new_tokens > self.max_len:
            raise InvalidArgumentError(
                "prompt %d + max_new_tokens %d exceeds cache max_len %d"
                % (len(ids), max_new_tokens, self.max_len))
        if max_new_tokens < 1:
            raise InvalidArgumentError("max_new_tokens must be >= 1")
        # fail at SUBMIT time, not mid-refill: a prompt no bucket covers
        # would otherwise raise after the slot bookkeeping started.
        # Chunked prefill needs no bucket at all — every prompt is
        # processed as fixed-shape [C] chunks, so prompts past the
        # largest bucket are servable there
        if self._chunk_tokens is None:
            self._session._bucket_for(len(ids))
        if self._layout.paged:
            # a request must fit an EMPTY pool — one SHARD's partition,
            # since a slot's blocks never span shards — else _refill
            # could never admit it and the pool would stall forever on
            # a full queue
            need = self._blocks_needed(len(ids), max_new_tokens)
            if need > self._blocks_per_shard - 1:
                raise InvalidArgumentError(
                    "request needs %d KV blocks (prompt %d + "
                    "max_new_tokens %d at block_size %d) but one dp "
                    "shard has only %d allocatable blocks "
                    "(num_blocks=%d / dp=%d minus the reserved scratch "
                    "block; a request's blocks never span shards); "
                    "raise num_blocks or lower max_new_tokens"
                    % (need, len(ids), max_new_tokens, self._block_size,
                       self._blocks_per_shard - 1, self._num_blocks,
                       self._dp))
        # one id namespace for explicit and auto ids: explicit duplicates
        # are rejected, auto-assignment skips ids a caller already took
        # (a collision would silently overwrite the earlier results);
        # collected ids (returned by run()) become reusable
        if request_id is not None:
            if request_id in self._used_rids:
                raise DuplicateRequestError(
                    "request_id %r is already queued, active, or "
                    "awaiting collection; a duplicate would shadow the "
                    "earlier request's result" % (request_id,))
            rid = request_id
        else:
            while self._next_rid in self._used_rids:
                self._next_rid += 1
            rid = self._next_rid
            self._next_rid += 1
        self._used_rids.add(rid)
        self._seq += 1
        # _sampling is the internal resubmission seam: an already-
        # resolved config (with its non-zero ``draws`` stream offset)
        # passes through verbatim so a resubmitted prompt+committed
        # continues its original sampling stream byte-identically
        samp = _sampling if _sampling is not None else \
            self._resolve_sampling(temperature, top_k, top_p, seed)
        self._queue.append(_Request(rid, ids.astype(np.int32),
                                    int(max_new_tokens), int(priority),
                                    tenant, deadline, self._seq, samp,
                                    self._check_adapter(adapter)))
        return rid

    # -- mesh / shard mapping (docs §5k) ---------------------------------
    @property
    def mesh(self) -> Optional[DecodeMesh]:
        """The decode mesh (None for an unsharded pool)."""
        return self._mesh

    @property
    def dp_shards(self) -> int:
        """dp shards the slot axis is partitioned into (1 unsharded)."""
        return self._dp

    def _shard_of_slot(self, slot: int) -> int:
        """Logical slot -> dp shard: NamedSharding partitions the slot
        axis into equal CONTIGUOUS chunks in mesh order, so shard =
        slot // slots_per_shard and local slot = slot % slots_per_shard
        — the logical→(shard, local-slot) mapping the scheduler above
        never sees."""
        return slot // self._slots_per_shard

    def _shard_of_block(self, b: int) -> int:
        """Physical block -> dp shard (the block pool's leading axis is
        partitioned like the slot axis)."""
        return b // self._blocks_per_shard

    def _shard_scratch(self, shard: int) -> int:
        """Shard ``shard``'s reserved scratch block (its partition's
        first physical block; 0 when dp == 1 — the legacy scratch)."""
        return shard * self._blocks_per_shard

    def _spilled_dev_count(self, shard: int) -> int:
        """Device-resident spilled blocks reclaimable from ``shard``'s
        partition (they sit on top of its free list for admission
        math)."""
        if self._dp == 1:
            return len(self._spill_owner)
        return sum(1 for b in self._spill_owner
                   if self._shard_of_block(b) == shard)

    def _pop_free_slot(self, shard: Optional[int] = None) -> int:
        """Take a free slot — the LAST free one (matching the legacy
        ``self._free.pop()`` order), restricted to ``shard`` when the
        paged allocator needs the slot's blocks in a specific
        partition.  Callers check availability first."""
        self._alloc_version += 1
        if shard is None or self._dp == 1:
            return self._free.pop()
        for i in range(len(self._free) - 1, -1, -1):
            if self._shard_of_slot(self._free[i]) == shard:
                return self._free.pop(i)
        raise PreconditionNotMetError(
            "no free slot in dp shard %d (free slots: %s) — callers "
            "must check shard availability before popping"
            % (shard, sorted(self._free)))

    def _free_slot(self, slot: int) -> None:
        """Give ``slot`` back: every slot release, like every take,
        moves the allocator's version."""
        self._alloc_version += 1
        self._free.append(slot)

    @property
    def _free_blocks(self) -> List[int]:
        """Free-list view: with dp == 1 this IS the live shard-0 list
        (the legacy attribute tests and tools read); sharded pools get
        a flattened read-only copy — mutate through the per-shard
        lists."""
        if self._dp == 1:
            return self._free_by_shard[0]
        return [b for fl in self._free_by_shard for b in fl]

    def _new_cache(self):
        """Allocate the pool cache and (under a mesh) place every leaf
        by the §5k axis rules — K/V and scales sharded ('dp', 'mp'),
        table/index sharded ('dp') — so XLA compiles the decode step as
        per-shard programs with collectives only where mp requires
        them."""
        cache = self._model.gen_decode_cache(
            self.slots, self.max_len, self._cache_dtype, per_slot=True,
            layout=self._kv_layout, block_size=self._block_size,
            num_blocks=(self._num_blocks if self._layout.paged else None))
        if self._mesh is not None:
            cache = self._mesh.place_cache(cache)
        return cache

    def _blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Blocks a request reserves at ADMISSION: its worst-case token
        span (prompt + generated; submit caps it at max_len).  Reserving
        up front means a mid-decode step can never run out of blocks —
        the allocator's no-preemption invariant."""
        span = min(prompt_len + max_new_tokens, self.max_len)
        return -(-span // self._block_size)

    def _alloc_blocks(self, n: int, shard: int = 0) -> List[int]:
        """Pop ``n`` fresh blocks at refcount 1 from ``shard``'s
        partition: its free list first, then — under pressure —
        RECLAIM spilled device copies (lowest-priority victim first;
        its host copy is the survivor, so the preempted request stays
        resumable, just via the upload path)."""
        self._prefix_epoch += 1
        self._alloc_version += 1
        fl = self._free_by_shard[shard]
        blocks = []
        for _ in range(n):
            if not fl:
                self._reclaim_one_spilled(shard)
            blocks.append(fl.pop())
        for b in blocks:
            self._block_refs[b] = 1
        return blocks

    def _reclaim_one_spilled(self, shard: int = 0) -> None:
        """Drop ONE spilled block's device copy (from ``shard``'s
        partition) back to its free list (its owner's ``dev_blocks``
        entry goes None — resume for that block becomes a host
        upload).  Victim order: lowest priority, then oldest arrival —
        the least important parked request loses its zero-copy resume
        first."""
        owners = [sp for sp in self._spilled.values()
                  if sp.shard == shard
                  and any(b is not None for b in sp.dev_blocks)]
        if not owners:
            raise PreconditionNotMetError(
                "allocator invariant broken: no free block and no "
                "reclaimable spilled block in dp shard %d (callers "
                "must check availability before allocating)" % (shard,))
        sp = min(owners, key=lambda s: (s.priority, s.seq))
        j = next(i for i, b in enumerate(sp.dev_blocks) if b is not None)
        b = sp.dev_blocks[j]
        sp.dev_blocks[j] = None
        self._spill_owner.pop(b, None)
        self._free_by_shard[shard].append(b)
        self._alloc_version += 1
        self._spill_reclaims_total += 1

    def _drop_device_copies(self, sp) -> None:
        """Return a parked victim's still-device-resident spilled blocks
        to their free lists (its host copy, where one is kept, is the
        survivor)."""
        self._prefix_epoch += 1
        self._alloc_version += 1
        for b in sp.dev_blocks:
            if b is not None:
                self._spill_owner.pop(b, None)
                self._free_by_shard[self._shard_of_block(b)].append(b)

    def _forget_block_key(self, b: int) -> None:
        """Remove ``b`` from the prefix index (an index entry must
        always name a RESIDENT block — freed and spilled blocks both
        leave it)."""
        key = self._block_keys.pop(b, None)
        if key is not None:
            entry = self._prefix_index.get(key)
            if entry is not None:
                if b in entry.blocks:
                    entry.blocks.remove(b)
                if not entry.blocks:
                    del self._prefix_index[key]

    def _release_blocks(self, slot: int) -> None:
        """DECREF every block the slot's table row maps; blocks hitting
        refcount 0 return to the free list and leave the prefix index
        (an index entry must always name a RESIDENT block).  A block
        another slot still shares stays resident — the refcount is what
        makes mid-generation release safe under sharing."""
        if not self._layout.paged:
            return
        self._prefix_epoch += 1
        self._alloc_version += 1
        for b in self._slot_blocks.pop(slot, ()):
            left = self._block_refs.get(b, 1) - 1
            if left > 0:
                self._block_refs[b] = left
                continue
            self._block_refs.pop(b, None)
            self._free_by_shard[self._shard_of_block(b)].append(b)
            self._forget_block_key(b)

    def _finish(self, slot: int):
        state = self._active.pop(slot)
        tokens = np.asarray(state.tokens, np.int32)
        self._results[state.rid] = tokens
        reason = classify_finish(tokens, self.eos_id)
        self._finish_reasons[state.rid] = reason
        self._free_slot(slot)
        # refcount-0 blocks are immediately reusable: the slot's stale
        # table row is masked to the scratch block inside every decode
        # step until a refill overwrites it; shared blocks stay resident
        self._release_blocks(slot)
        if self.on_finish is not None:
            self.on_finish(state.rid, tokens, reason)

    def release(self, slot: int):
        """Free ``slot`` (decref'ing its paged blocks) WITHOUT recording
        a result — the cancellation path, covering both DECODING and
        (chunked) still-PREFILLING slots.  Mid-generation release is as
        safe as ``_finish``: the freed slot's stale table row is masked
        to the scratch block inside every decode step until a refill
        overwrites it, and shared blocks outlive the release via their
        refcount.  Returns the request id the slot was serving."""
        self._settle()
        state = self._active.pop(slot, None) \
            or self._prefilling.pop(slot, None)
        if state is None:
            raise NotFoundError(
                "slot %r is not active or prefilling (active slots: "
                "%s, prefilling: %s)"
                % (slot, sorted(self._active), sorted(self._prefilling)))
        self._free_slot(slot)
        self._release_blocks(slot)
        self._used_rids.discard(state.rid)
        return state.rid

    def cancel(self, request_id):
        """Abort one request wherever it lives: ``"queued"`` (removed
        from the wait queue), ``"active"`` (its slot and paged blocks
        freed mid-generation — chunked mid-PREFILL slots count as
        active), or ``"finished"`` (the uncollected result discarded).
        The ``on_finish`` hook does NOT fire — cancellation is the
        caller's decision, not a completion.  Unknown ids raise
        :class:`NotFoundError`."""
        for i, req in enumerate(self._queue):
            if req.rid == request_id:
                del self._queue[i]
                self._used_rids.discard(request_id)
                return "queued"
        # past the queue the request may hold a slot: level with the
        # device first (it may have ended in the step in flight)
        self._settle()
        for slot, state in list(self._active.items()) \
                + list(self._prefilling.items()):
            if state.rid == request_id:
                self.release(slot)
                return "active"
        sp = self._spilled.pop(request_id, None)
        if sp is not None:
            # a parked victim dies in place: its still-device-resident
            # spilled blocks return to the free list, its host copies
            # drop with the record
            self._drop_device_copies(sp)
            self._used_rids.discard(request_id)
            self._spill_drop(sp)
            return "preempted"
        parked = self._prefill_done.pop(request_id, None)
        if parked is not None:
            # a prefill-complete request cancelled before export: its
            # slot and blocks free like an active cancel (no transfer
            # file exists yet — export_kv writes it)
            slot, _st = parked
            self._free_slot(slot)
            self._release_blocks(slot)
            self._used_rids.discard(request_id)
            return "prefill-done"
        if request_id in self._results:
            del self._results[request_id]
            self._finish_reasons.pop(request_id, None)
            self._used_rids.discard(request_id)
            return "finished"
        raise NotFoundError(
            "request_id %r is not queued, active, or awaiting "
            "collection" % (request_id,))

    def collect(self, request_id):
        """Pop ONE finished request's ``(tokens, finish_reason)`` —
        per-request collection for the serving layer, where ``run()``'s
        drain-everything loop would block on other callers' requests."""
        if request_id not in self._results:
            raise NotFoundError(
                "request_id %r has no finished result (still queued or "
                "active, cancelled, or already collected)"
                % (request_id,))
        tokens = self._results.pop(request_id)
        self._used_rids.discard(request_id)
        return tokens, self._finish_reasons.pop(request_id, None)

    def advance_auto_rids(self, floor: int) -> None:
        """Never auto-assign a request id below ``floor``.  The serving
        engine calls this when it opens a pre-existing journal: the
        crashed engine's auto int rids are TAKEN (their identities must
        replay untouched), and this pool's own pre-restore traffic must
        not reuse them in the shared file."""
        self._next_rid = max(self._next_rid, int(floor))

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (admission-control surface)."""
        return len(self._queue)

    @property
    def active_count(self) -> int:
        """Slots currently decoding."""
        return len(self._active)

    @property
    def prefilling_count(self) -> int:
        """Slots admitted under chunked prefill whose prompt is still
        being processed (0 on a non-chunked pool)."""
        return len(self._prefilling)

    @property
    def prefill_chunk_tokens(self) -> Optional[int]:
        """The per-tick prompt-work bound (None = one-shot prefill)."""
        return self._chunk_tokens

    @property
    def preempted_count(self) -> int:
        """Requests parked in the host-RAM spill tier."""
        return len(self._spilled)

    # -- preemption / host-RAM spill tier (docs §5j) ---------------------
    def _preempt_guard(self, slot: int, st: _SlotState) -> None:
        """Subclass veto point: raise a typed error when this slot
        cannot be safely preempted (the speculative pool requires draft
        bucket coverage for the resume-time re-prefill)."""

    def can_preempt(self, request_id) -> bool:
        """True when ``preempt(request_id)`` would succeed right now:
        the request is actively DECODING on a spillable layout (paged
        or recurrent) and every subclass resume precondition holds.
        The serving engine's degradation ladder filters victims through
        this instead of catching mid-tick errors."""
        if not self._layout.spillable:
            return False
        self._settle()
        for slot, st in self._active.items():
            if st.rid == request_id:
                try:
                    self._preempt_guard(slot, st)
                except Exception:  # noqa: BLE001 - veto, reason unused
                    return False
                return True
        return False

    def preempt(self, request_id) -> dict:
        """Evict one actively-decoding request, spilling its K/V to the
        host-RAM tier; returns an info dict (``blocks_spilled``,
        ``blocks_freed``, ``spill_bytes``, ``committed_tokens``).

        The victim's WRITTEN blocks are downloaded in one batched
        ``device_get`` (the deliberate spill-boundary host sync —
        int8 K/V and their fp32 scales ride together), then every
        block the victim held is decref'd: exclusively-owned written
        blocks move to the SPILLED tier (device content intact,
        reclaimable under pressure), unwritten reservation blocks go
        straight to the free list (nothing to keep), and prefix-shared
        blocks stay resident under their other owners (the host copy
        is the victim's restorable source).  The slot is freed; resume
        happens through ``_refill`` under the normal priority
        ordering.  Host-side bookkeeping plus eager array ops only —
        no tracked executable runs, so ``compile_counts()`` is
        unchanged (test-pinned)."""
        if not self._layout.spillable:
            raise PreconditionNotMetError(
                "preemption spills per-slot decode state to the host "
                "tier; " + self._layout.not_spillable())
        # the spill reads the victim's K/V and its committed count: both
        # must be level with the device
        self._settle()
        slot = next((s for s, st in self._active.items()
                     if st.rid == request_id), None)
        if slot is None:
            raise NotFoundError(
                "request_id %r is not actively decoding (queued, "
                "prefilling, already-preempted and finished requests "
                "cannot be preempted; active: %s)"
                % (request_id,
                   sorted(str(st.rid) for st in self._active.values())))
        st = self._active[slot]
        self._preempt_guard(slot, st)
        if not self._layout.paged:
            return self._preempt_recurrent(slot, st)
        bs = self._block_size
        shard = self._shard_of_slot(slot)
        # K/V are written for positions [0, pos): the last committed
        # token's K/V is NOT yet written (it is the next step's input)
        pos = len(st.ids) + len(st.tokens) - 1
        written = -(-pos // bs)
        blocks = self._slot_blocks.pop(slot)
        # the gather index is padded to a power-of-two bucket so the
        # eager gather compiles O(log max_blocks) distinct shapes over
        # the pool's lifetime, not one per victim length — padding rows
        # read the slot's shard's scratch block, harmless and never
        # restored
        padded_n = _pow2_at_least(written)
        gidx = np.full(padded_n, self._shard_scratch(shard), np.int32)
        gidx[:written] = blocks[:written]
        gather = jnp.asarray(gidx)
        # ONE batched download of everything resume must be able to
        # restore — the spill boundary's deliberate host sync.  An entry
        # that is not paged (a recurrent layer of a model that mixes
        # kinds) gives the slot's state rows whole
        lays = self._layout.layouts(self._cache)
        host = jax.device_get([
            tuple(getattr(c, f)[gather] for f in lay.payload_fields(c))
            if lay.paged else
            tuple(getattr(c, f)[slot] for f in lay.state_fields(c))
            for lay, c in zip(lays, self._cache)])
        # honest byte accounting: the pad rows are not spilled content
        state_bytes = sum(arr.nbytes for lay, layer in zip(lays, host)
                          if not lay.paged for arr in layer)
        host_bytes = state_bytes + sum(
            arr[:written].nbytes for lay, layer in zip(lays, host)
            if lay.paged for arr in layer)
        host_path = None
        if self.spill_tier == "disk":
            # the disk write happens BEFORE any allocator mutation, so
            # a failed write (the `spill.write` injection seam, or a
            # real EIO/full disk) leaves the pool exactly as it was —
            # the victim keeps decoding, nothing to unwind
            try:
                host_path = self._spill_write(st, host, written)
            except BaseException:
                self._slot_blocks[slot] = blocks
                raise
            host = None  # the file is the survivor, not process RAM
        self._active.pop(slot)
        self._free_slot(slot)
        self._prefix_epoch += 1
        sp = _SpillState(st, len(blocks), written, host, host_bytes,
                         shard=shard)
        sp.host_path = host_path
        freed = 0
        for j, b in enumerate(blocks):
            left = self._block_refs.get(b, 1) - 1
            if left > 0:
                # prefix-shared: other owners keep it resident; the
                # victim restores from its host copy at resume
                self._block_refs[b] = left
                continue
            self._block_refs.pop(b, None)
            self._forget_block_key(b)
            if j < written:
                self._spill_owner[b] = (st.rid, j)
                sp.dev_blocks[j] = b
            else:
                self._free_by_shard[shard].append(b)
                freed += 1
        self._spilled[st.rid] = sp
        self._preempts_total += 1
        self._spill_bytes_total += host_bytes
        info = {"rid": st.rid, "slot": slot, "blocks_spilled": written,
                "blocks_freed": freed, "spill_bytes": host_bytes,
                "committed_tokens": len(st.tokens)}
        if self._layout.recurrent:
            info["state_bytes"] = state_bytes
        return info

    def _preempt_recurrent(self, slot: int, st: _SlotState) -> dict:
        """Recurrent-layout preemption: the victim's entire decode
        state is one ``[layers, d_state]`` carry — download the slot's
        state rows in one ``device_get`` (the same spill-boundary sync
        as the paged gather, minus the gather: there are no blocks),
        park it in the host/disk tier, and free the slot.  No allocator
        interaction at all; resume uploads the carry into any free slot
        and greedy decode continues byte-identically."""
        # the carry covers positions [0, pos): the last committed token
        # is the next step's input, exactly the positional convention
        fields = self._layout.state_fields(self._cache[0])
        host = jax.device_get([tuple(getattr(c, f)[slot] for f in fields)
                               for c in self._cache])
        host_bytes = sum(arr.nbytes for layer in host for arr in layer)
        host_path = None
        if self.spill_tier == "disk":
            # write BEFORE any pool mutation (the paged ordering): a
            # failed write leaves the victim decoding, nothing to unwind
            host_path = self._spill_write(st, host, written=0)
            host = None
        self._active.pop(slot)
        self._free_slot(slot)
        sp = _SpillState(st, 0, 0, host, host_bytes,
                         shard=self._shard_of_slot(slot))
        sp.host_path = host_path
        self._spilled[st.rid] = sp
        self._preempts_total += 1
        self._spill_bytes_total += host_bytes
        return {"rid": st.rid, "slot": slot, "blocks_spilled": 0,
                "blocks_freed": 0, "spill_bytes": host_bytes,
                "state_bytes": host_bytes,
                "committed_tokens": len(st.tokens)}

    def _resume_recurrent(self, sp: _SpillState) -> None:
        """Re-activate a recurrent-layout victim: page the carry in
        (host tier: process RAM; disk tier: the PTKV transfer file,
        with the per-victim bad-file fallback — drop the spill and
        resubmit prompt+committed, byte-identical either way), upload
        it into any free slot's state row, and restore the index and
        last-token input."""
        host_src = sp.host
        if host_src is None:
            try:
                host_src = self._spill_read(sp)
            except Exception:  # noqa: BLE001 - per-victim fallback
                self._spill_drop(sp)
                self._used_rids.discard(sp.rid)
                ids = np.concatenate(
                    [sp.ids, np.asarray(sp.tokens, np.int32)])
                self.submit(ids, sp.remaining, request_id=sp.rid,
                            priority=sp.priority, tenant=sp.tenant,
                            deadline=sp.deadline, adapter=sp.adapter,
                            _sampling=self._resubmit_sampling(
                                sp.sampling, len(sp.tokens)))
                return
        # any free slot works: the carry has no shard-resident blocks
        # pinning it (state rows shard over dp, but an upload into any
        # row is just a placed scatter)
        slot = self._pop_free_slot()
        pos = len(sp.ids) + len(sp.tokens) - 1
        pos_dev = jnp.asarray(pos, jnp.int32)
        fields = self._layout.state_fields(self._cache[0])
        self._cache = [
            c._replace(index=c.index.at[slot].set(pos_dev),
                       **{f: getattr(c, f).at[slot].set(
                              jnp.asarray(host_src[layer][j]))
                          for j, f in enumerate(fields)})
            for layer, c in enumerate(self._cache)]
        state = _SlotState(sp.rid, sp.ids, sp.tokens, sp.remaining,
                           priority=sp.priority, tenant=sp.tenant,
                           deadline=sp.deadline, seq=sp.seq,
                           sampling=sp.sampling, adapter=sp.adapter)
        self._active[slot] = state
        self._patch_carry(slot, np.int32(sp.tokens[-1]), state)
        self._resumes_total += 1
        self._upload_bytes_total += sp.host_bytes
        self._spill_drop(sp)
        self._on_resumed(slot, sp)
        if self.on_resume is not None:
            self.on_resume(sp.rid, {
                "slot": slot, "blocks_remapped": 0, "blocks_uploaded": 0,
                "state_bytes": sp.host_bytes,
                "committed_tokens": len(sp.tokens)})

    def _resume(self, sp: _SpillState) -> None:
        """Re-activate one parked request into a free slot: re-map its
        still-device-resident spilled blocks IN PLACE (zero copy),
        allocate fresh blocks for everything else and upload the host
        copies of reclaimed/shared written blocks into them, then
        restore the table row, cache index and last-token input.  The
        restored K/V are bit-exact, so greedy decode continues
        byte-identically (eager array ops only — no tracked compile)."""
        # page the host copy in BEFORE any allocator mutation: the
        # disk-tier file can vanish or corrupt between park and resume
        # (operator cleanup, a shared-dir consumer, EIO), and failing
        # AFTER the slot/blocks were assigned would escalate one bad
        # file into a whole-pool recovery.  adopt_spill's own rule
        # applies — resubmit is always available and always correct —
        # so the loss is contained to THIS victim: its device copies
        # free, and prompt+committed re-queues under its identity.
        if not self._layout.paged:
            return self._resume_recurrent(sp)
        host_src = sp.host
        if host_src is None and any(
                sp.dev_blocks[j] is None for j in range(sp.written)):
            try:
                host_src = self._spill_read(sp)
            except Exception:  # noqa: BLE001 - per-victim fallback
                self._drop_device_copies(sp)
                self._spill_drop(sp)
                self._used_rids.discard(sp.rid)
                ids = np.concatenate(
                    [sp.ids, np.asarray(sp.tokens, np.int32)])
                self.submit(ids, sp.remaining, request_id=sp.rid,
                            priority=sp.priority, tenant=sp.tenant,
                            deadline=sp.deadline, adapter=sp.adapter,
                            _sampling=self._resubmit_sampling(
                                sp.sampling, len(sp.tokens)))
                return
        slot = self._pop_free_slot(sp.shard)
        blocks: List[int] = []
        upload: List[tuple] = []  # (logical j, physical block)
        for j in range(sp.total_blocks):
            b = sp.dev_blocks[j] if j < sp.written else None
            if b is not None:
                # fast path: the device copy survived — re-map it
                self._spill_owner.pop(b, None)
                self._block_refs[b] = 1
                blocks.append(b)
            else:
                nb = self._alloc_blocks(1, sp.shard)[0]
                blocks.append(nb)
                if j < sp.written:
                    upload.append((j, nb))
        self._slot_blocks[slot] = blocks
        pos = len(sp.ids) + len(sp.tokens) - 1
        scratch = self._shard_scratch(sp.shard)
        padded = np.full(self._max_blocks, scratch, np.int32)
        padded[:len(blocks)] = blocks
        row = jnp.asarray(padded)
        pos_dev = jnp.asarray(pos, jnp.int32)
        if upload:
            # same power-of-two padding discipline as the spill gather:
            # pad target ids with the shard's scratch block, whose
            # write lands there — garbage in scratch is the §5b masking
            # contract
            n_up = len(upload)
            padded_n = _pow2_at_least(n_up)
            sel = np.zeros(padded_n, np.intp)
            sel[:n_up] = [j for j, _ in upload]
            ids = np.full(padded_n, scratch, np.int32)
            ids[:n_up] = [b for _, b in upload]
            ids_dev = jnp.asarray(ids)
        new_cache = []
        lays = self._layout.layouts(self._cache)
        for layer, (lay, c) in enumerate(zip(lays, self._cache)):
            if not lay.paged:
                # a recurrent layer of a model that mixes kinds: its
                # state rows come back whole from the host copy (the
                # host tier always holds one: such a layout has no disk
                # tier)
                new_cache.append(c._replace(
                    index=c.index.at[slot].set(pos_dev),
                    **{f: getattr(c, f).at[slot].set(
                           jnp.asarray(host_src[layer][j]))
                       for j, f in enumerate(lay.state_fields(c))}))
                continue
            upd = dict(table=c.table.at[slot].set(row),
                       index=c.index.at[slot].set(pos_dev))
            if upload:
                # the payload fields in the order the spill took them
                # (K, V and an int8 pool's scales; a latent)
                for f, arr in zip(lay.payload_fields(c), host_src[layer]):
                    upd[f] = getattr(c, f).at[ids_dev].set(
                        jnp.asarray(arr[sel]))
            new_cache.append(c._replace(**upd))
        self._cache = new_cache
        state = _SlotState(sp.rid, sp.ids, sp.tokens, sp.remaining,
                           priority=sp.priority, tenant=sp.tenant,
                           deadline=sp.deadline, seq=sp.seq,
                           sampling=sp.sampling, adapter=sp.adapter)
        self._active[slot] = state
        self._patch_carry(slot, np.int32(sp.tokens[-1]), state)
        self._prefix_epoch += 1
        self._resumes_total += 1
        if upload:
            # honest byte accounting: pad rows are not paged-in content
            self._upload_bytes_total += sum(
                arr[sel[:n_up]].nbytes
                for lay, fields in zip(lays, host_src) if lay.paged
                for arr in fields)
        state_bytes = sum(arr.nbytes for lay, fields in zip(lays, host_src)
                          if not lay.paged for arr in fields) \
            if self._layout.recurrent else 0
        self._upload_bytes_total += state_bytes
        # the parked copy is consumed: a disk-tier file is deleted the
        # moment its request decodes again (a crash after this point
        # restores via the journal's prompt+committed replay instead)
        self._spill_drop(sp)
        self._on_resumed(slot, sp)
        if self.on_resume is not None:
            info = {
                "slot": slot, "blocks_remapped": len(blocks) - len(upload)
                - (sp.total_blocks - sp.written),
                "blocks_uploaded": len(upload),
                "committed_tokens": len(sp.tokens)}
            if self._layout.recurrent:
                info["state_bytes"] = state_bytes
            self.on_resume(sp.rid, info)

    def _on_resumed(self, slot: int, sp: _SpillState) -> None:
        """Subclass hook: a preempted request just resumed decoding in
        ``slot`` with its K/V restored.  The speculative pool re-prefills
        its draft twin here."""

    def spill_stats(self) -> dict:
        """Host-side spill-tier accounting — what the serving gauges
        (``serving_spilled_*``) read.
        ``spilled_blocks_device`` counts reclaimable device-resident
        spilled copies (part of the exact free/resident/spilled/scratch
        partition of ``num_blocks``); ``spilled_blocks_host`` counts
        written blocks whose content is held host-side (every spilled
        request's written span, device-resident or not)."""
        return {
            "enabled": self._layout.spillable,
            "spill_tier": self.spill_tier,
            "preempts_total": self._preempts_total,
            "resumes_total": self._resumes_total,
            "spilled_requests": len(self._spilled),
            "spilled_blocks_device": len(self._spill_owner),
            "spilled_blocks_host": sum(sp.written
                                       for sp in self._spilled.values()),
            "spill_bytes_total": self._spill_bytes_total,
            "upload_bytes_total": self._upload_bytes_total,
            "reclaims_total": self._spill_reclaims_total,
        }

    # -- disk spill backend (docs §5m) -----------------------------------
    def _spill_path(self, rid) -> str:
        """The .npz a request's spilled K/V lives in — a pure function
        of the rid, so a SECOND engine pointed at the same directory
        finds a crashed engine's files.  The type tag keeps int 1 and
        str "1" from colliding on one file."""
        tag = "i" if isinstance(rid, (int, np.integer)) else "s"
        safe = "".join(c if c.isalnum() or c in "-_" else "~%02x" % ord(c)
                       for c in str(rid))
        return os.path.join(self._spill_dir,
                            "spill-%s%s.npz" % (tag, safe))

    def _spill_write(self, st: _SlotState, host, written: int,
                     seam: str = "spill.write") -> str:
        """Write one request's gathered K/V (+ int8 scales — they ride
        their blocks) to its transfer file under the versioned
        ``serving.transfer`` contract (PTKV magic + version + this
        pool's config fingerprint in the header); the writer keeps the
        tmp file + fsync + atomic rename discipline, so a crash
        mid-write can never leave a half file an adopting engine would
        read.  Fires ``seam`` (``spill.write`` for preemption spills,
        ``xfer.write`` for prefill-tier exports); a transient failure
        is retried ONCE (each caught fault emits a ``spill.error`` /
        ``xfer.error`` trace event, so the chaos harness reconciles
        injections against the recorder), then propagates — the caller
        leaves the pool untouched."""
        path = self._spill_path(st.rid)
        arrays = {}
        recurrent = not self._layout.paged
        for i, layer in enumerate(host):
            for j, arr in enumerate(layer):
                # recurrent payload is whole state rows, not a written-
                # blocks prefix (written == 0 by convention there)
                arrays["l%d_f%d" % (i, j)] = (arr if recurrent
                                              else arr[:written])
        cfg = st.sampling if st.sampling is not None \
            else _SamplingConfig(0.0, 0, 1.0, 0)
        meta = {"rid": str(st.rid), "prompt_len": int(len(st.ids)),
                "committed": len(st.tokens), "written": int(written),
                "cache_layout": self.cache_layout,
                "layers": len(host), "fields": len(host[0]),
                "cache_dtype": self._layout.cache_dtype_str(self._cache),
                # the as-data config rides the transfer header (docs
                # §5q): the adopting engine resumes the victim under
                # ITS OWN sampling stream and adapter, not the peer's
                # defaults
                "sampling": [float(cfg.temperature), int(cfg.top_k),
                             float(cfg.top_p), int(cfg.seed),
                             int(cfg.draws)],
                "adapter": int(st.adapter)}
        if recurrent:
            meta.update(self._layout.fingerprint_extra(self))
        else:
            meta["block_size"] = self._block_size
        return _transfer_mod().write_transfer(
            path, self.config_fingerprint(), meta, arrays,
            seam=seam, rid=st.rid)

    def _spill_read(self, sp: _SpillState):
        """Map a disk-tier transfer file back into the per-layer tuple
        shape ``_resume``'s upload path consumes.  The reader is
        mmap-backed: the returned arrays are zero-copy views, so the
        only copy is the device upload itself (the views keep the
        mapping alive)."""
        r = _transfer_mod().TransferReader(sp.host_path)
        meta = r.meta
        return [tuple(r.arrays["l%d_f%d" % (i, j)]
                      for j in range(meta["fields"]))
                for i in range(meta["layers"])]

    def _spill_drop(self, sp: _SpillState) -> None:
        """Delete a spill record's disk file, if it has one (resume /
        cancel / reset all consume the parked copy; no-op on the host
        tier)."""
        path = sp.host_path
        if path is not None:
            sp.host_path = None
            try:
                os.remove(path)
            except OSError:
                pass

    def _adopt_guard(self, ids, tokens) -> None:
        """Subclass veto for :meth:`adopt_spill` — the speculative pool
        requires draft bucket coverage for the resume-time re-prefill,
        the same constraint ``_preempt_guard`` imposes at preempt
        time."""

    def adopt_spill(self, request_id, input_ids, tokens,
                    max_new_tokens: int, priority: int = 0, tenant=None,
                    deadline=None) -> bool:
        """Adopt a crashed engine's disk-spilled K/V for ``request_id``:
        park the request in this pool's spill tier with its ``.npz`` as
        the restorable source, so the next refill resumes it through
        the normal upload path — no re-prefill, byte-identical (the
        file holds bit-exact K/V for positions ``[0, prompt+committed-1)``,
        the exact resume state).

        Returns False — the caller falls back to prompt+committed
        resubmit — whenever adoption cannot be exact: tier off, no
        file, a file whose meta disagrees with the journal's committed
        count (the victim decoded past its last spill before crashing —
        the file is STALE), shape/dtype/block-size mismatch against
        this pool's cache, or a subclass veto.  Never raises for a bad
        file: resubmit is always available and always correct."""
        if self.spill_tier != "disk" or not self._layout.transferable:
            return False
        if request_id in self._used_rids:
            return False
        ids = np.asarray(getattr(input_ids, "value",
                                 input_ids)).astype(np.int32)
        tokens = [int(t) for t in tokens]
        # a parked request by construction has >= 1 committed token and
        # >= 1 remaining (otherwise it would have finished, and replay
        # finalizes it instead of resubmitting)
        if len(tokens) < 1 or int(max_new_tokens) - len(tokens) < 1:
            return False
        path = self._spill_path(request_id)
        if not os.path.exists(path):
            return False
        recurrent = not self._layout.paged
        first = self._cache[0]
        if recurrent:
            # the carry is O(1): no block math, no capacity gate — a
            # free slot is the only resource resume needs
            written = total = 0
            nf = len(self._layout.state_fields(first))
        else:
            bs = self._block_size
            pos = int(len(ids)) + len(tokens) - 1
            written = -(-pos // bs)
            total = self._blocks_needed(len(ids), int(max_new_tokens))
            if total > self._blocks_per_shard - 1:
                return False
            nf = 4 if first.k_scale is not None else 2
        xfer = _transfer_mod()
        try:
            r = xfer.TransferReader(path)
        except xfer.TransferVersionError as e:
            # a PTKV file under OUR rid naming in an OLDER format
            # version can never be adopted again — delete it, the
            # stale-file litter rule; a NEWER version is a newer
            # engine's file sharing the dir, not ours to judge
            if e.found < xfer.VERSION:
                try:
                    os.remove(path)
                except OSError:
                    pass
            from ..serving import log as _slog
            _slog.emit("xfer.reject", rid=str(request_id),
                       reason="version", found=e.found,
                       deleted=e.found < xfer.VERSION)
            return False
        except xfer.TransferFormatError as e:
            # pre-upgrade unversioned np.savez spill (or a corrupt
            # file): detected and rejected with a one-line log, never
            # a crash — and left on disk, the old engine's to clean up
            from ..serving import log as _slog
            _slog.emit("xfer.reject", rid=str(request_id),
                       reason="legacy_npz" if e.legacy_npz
                       else "format", detail=str(e))
            return False
        except Exception:  # noqa: BLE001 - a bad file falls back, always
            return False
        try:
            try:
                xfer.check_fingerprint(r.fingerprint,
                                       self.config_fingerprint())
            except xfer.TransferFingerprintError as e:
                # another deployment's file (different sampling/cache
                # semantics) sharing the dir — fall back without
                # deleting what is not ours to judge
                from ..serving import log as _slog
                _slog.emit("xfer.reject", rid=str(request_id),
                           reason="fingerprint", keys=list(e.keys))
                return False
            meta = r.meta
            if (meta.get("committed") != len(tokens)
                    or meta.get("prompt_len") != len(ids)
                    or meta.get("written") != written):
                # STALE: the journal is ground truth, and a file
                # whose resume point disagrees with it can never
                # be adopted again — delete it, or crash/restore
                # cycles accumulate dead transfer-file litter (and
                # stale K/V under a recurring rid is worse than no
                # file, the reset() rule)
                try:
                    os.remove(path)
                except OSError:
                    pass
                return False
            structural_ok = (
                meta.get("layers") == len(self._cache)
                and meta.get("fields") == nf
                and meta.get("cache_dtype")
                == self._layout.cache_dtype_str(self._cache))
            if recurrent:
                geometry = self._layout.fingerprint_extra(self)
                structural_ok = (
                    structural_ok
                    and all(meta.get(k) == v for k, v in geometry.items())
                    and tuple(r.arrays["l0_f0"].shape)
                    == tuple(first.state.shape[1:]))
            else:
                structural_ok = (
                    structural_ok
                    and meta.get("block_size") == bs
                    and tuple(r.arrays["l0_f0"].shape)
                    == (written,) + tuple(first.k.shape[1:]))
            if not structural_ok:
                # structural mismatch against THIS pool's cache:
                # possibly another config's pool sharing the dir —
                # fall back without deleting what is not ours to
                # judge
                return False
            host_bytes = int(r.nbytes)
        except Exception:  # noqa: BLE001 - a bad file falls back, always
            return False
        try:
            self._adopt_guard(ids, tokens)
        except Exception:  # noqa: BLE001 - subclass veto -> resubmit
            return False
        # the victim's as-data config from the transfer header: resume
        # continues ITS stream (seed, draws+committed) and ITS adapter.
        # An adapter this pool's bank cannot address (no bank, or id out
        # of range) falls back — the fleet hot-loads before retrying
        msamp = meta.get("sampling")
        sampling = None if msamp is None else _SamplingConfig(
            float(msamp[0]), int(msamp[1]), float(msamp[2]),
            int(msamp[3]), int(msamp[4]) if len(msamp) > 4 else 0)
        try:
            adapter = self._check_adapter(meta.get("adapter", 0))
        except InvalidArgumentError:
            return False
        self._seq += 1
        st = _SlotState(request_id, ids, tokens,
                        int(max_new_tokens) - len(tokens),
                        priority=int(priority), tenant=tenant,
                        deadline=deadline, seq=self._seq,
                        sampling=sampling, adapter=adapter)
        # no device-resident copies to pin the shard: park where the
        # most blocks are free (dp == 1: shard 0, the common case;
        # recurrent carries need no blocks at all — any slot works)
        shard = 0 if recurrent else max(
            range(self._dp),
            key=lambda s: len(self._free_by_shard[s]))
        sp = _SpillState(st, total, written, None, host_bytes,
                         shard=shard)
        sp.host_path = path
        self._spilled[request_id] = sp
        self._used_rids.add(request_id)
        return True

    def detach_spilled(self, request_id) -> dict:
        """Release a disk-parked victim from this pool KEEPING its
        transfer file — the live-migration donor primitive.  Where
        ``cancel()`` on a preempted request deletes the spill file with
        the record (the request is dead), detach forgets the request but
        leaves the ``.npz`` on disk for a peer engine sharing the spill
        directory to ``adopt_spill`` under the same rid: still-resident
        device copies return to the free list (the host file is the
        only restorable source from here on), the rid leaves
        ``_used_rids`` so this pool could even re-admit it later.
        Disk tier only: a host-RAM-parked victim has no file to hand
        over (``PreconditionNotMetError`` — the caller falls back to
        prompt+committed resubmit, byte-identical either way)."""
        sp = self._spilled.get(request_id)
        if sp is None:
            raise NotFoundError(
                "request_id %r is not parked in the spill tier"
                % (request_id,))
        if sp.host_path is None:
            raise PreconditionNotMetError(
                "request %r is parked on the host tier (no transfer "
                "file) — only disk-tier victims detach for migration"
                % (request_id,))
        del self._spilled[request_id]
        self._drop_device_copies(sp)
        self._used_rids.discard(request_id)
        path, sp.host_path = sp.host_path, None
        return {"rid": request_id, "path": path,
                "committed_tokens": len(sp.tokens),
                "spill_bytes": sp.host_bytes}

    @property
    def prefill_done_count(self) -> int:
        """Prefill-complete requests parked awaiting export (always 0
        unless ``prefill_only=True``)."""
        return len(self._prefill_done)

    def has_prefill_done(self, request_id) -> bool:
        """True while ``request_id`` is parked prefill-complete (not
        yet exported or cancelled)."""
        return request_id in self._prefill_done

    def export_kv(self, request_id) -> dict:
        """First-class K/V export of a parked prefill-complete request
        through the transfer contract (docs §5n): gather its written
        blocks (+ int8 scales) in ONE batched download — the same
        pow2-padded gather ``preempt`` compiles, so export adds no new
        eager shapes — write them to the request's transfer file at the
        ``xfer.write`` seam, then free the slot and blocks.  NO
        preemption semantics: there is no victim, no resume
        bookkeeping, no ``_spilled`` entry — the file plus the returned
        committed state IS the hand-off, and the adopting decode-tier
        pool re-parks it via :meth:`adopt_spill` (one mechanism for
        migration, restore, and disaggregation).

        The write happens BEFORE any allocator mutation, so a failed
        write (the ``xfer.write`` injection seam, or a real EIO) leaves
        the request parked and the pool untouched — the caller can
        retry or fall back to prompt+committed hand-off.  Unknown or
        not-parked ids raise :class:`NotFoundError`."""
        self._settle()
        parked = self._prefill_done.get(request_id)
        if parked is None:
            raise NotFoundError(
                "request_id %r is not parked prefill-complete (not a "
                "prefill_only pool, not yet prefilled, cancelled, or "
                "already exported)" % (request_id,))
        slot, st = parked
        shard = self._shard_of_slot(slot)
        blocks = self._slot_blocks[slot]
        pos = len(st.ids) + len(st.tokens) - 1
        written = -(-pos // self._block_size)
        padded_n = _pow2_at_least(written)
        gidx = np.full(padded_n, self._shard_scratch(shard), np.int32)
        gidx[:written] = blocks[:written]
        gather = jnp.asarray(gidx)
        host = jax.device_get([
            (c.k[gather], c.v[gather])
            + ((c.k_scale[gather], c.v_scale[gather])
               if c.k_scale is not None else ())
            for c in self._cache])
        # honest byte accounting: the pad rows are not hand-off content
        transfer_bytes = sum(arr[:written].nbytes
                             for layer in host for arr in layer)
        path = self._spill_write(st, host, written, seam="xfer.write")
        del self._prefill_done[request_id]
        self._free_slot(slot)
        self._release_blocks(slot)
        self._used_rids.discard(request_id)
        cfg = st.sampling if st.sampling is not None \
            else _SamplingConfig(0.0, 0, 1.0, 0)
        return {"rid": request_id, "path": path,
                "transfer_bytes": int(transfer_bytes),
                "blocks_written": int(written),
                "committed_tokens": len(st.tokens),
                "prompt_len": int(len(st.ids)),
                "max_new_tokens": len(st.tokens) + st.remaining,
                "priority": st.priority, "tenant": st.tenant,
                "deadline": st.deadline,
                "sampling": [float(cfg.temperature), int(cfg.top_k),
                             float(cfg.top_p), int(cfg.seed),
                             int(cfg.draws)],
                "adapter": int(st.adapter)}

    def config_fingerprint(self) -> dict:
        """The JSON-stable identity of everything byte-identical replay
        depends on: the cache layout/dtype/geometry, the mesh shape,
        and — since sampling became per-request data (docs §5q) — the
        SAMPLING DISCIPLINE marker plus the LoRA bank geometry, never
        the config values themselves.  The engine-global
        temperature/top_k/top_p/sampling_seed fields of the v1
        fingerprint are GONE: every journal record / spill meta carries
        its request's own resolved config, so two engines with
        different defaults replay each other's journals byte-
        identically.  Written into every journal's header;
        ``ServingEngine.restore`` refuses a journal whose fingerprint
        differs, naming both sides (docs §5m) — with a one-shot upgrade
        triage for v1 journals whose ONLY difference is the dropped
        sampling fields."""
        fp = {
            "pool_type": type(self).__name__,
            # the discipline marker: a v1 peer (config-global sampling
            # baked into the executable) can never exchange journals or
            # K/V with a per-request pool, whatever its config said
            "sampling": "per-request",
            # bank GEOMETRY is compiled (shapes); contents are
            # hot-swappable rows and stay out on purpose
            "lora": (None if self._lora_cfg is None
                     else {"n_adapters": int(self._lora_cfg[0]),
                           "rank": int(self._lora_cfg[1])}),
            "eos_id": None if self.eos_id is None else int(self.eos_id),
            "max_len": self.max_len,
            "slots": self.slots,
            "vocab_size": (None if self._vocab is None
                           else int(self._vocab)),
            "cache_layout": self.cache_layout,
            "cache_dtype": self._layout.cache_dtype_str(self._cache),
            "mesh": (None if self._mesh is None
                     else {"dp": int(self._mesh.dp),
                           "mp": int(self._mesh.mp)}),
        }
        # layout geometry (paged: block_size/num_blocks; recurrent:
        # d_state) — carried so a transformer engine can never adopt a
        # recurrent engine's spill file or journal, and vice versa
        # (check_fingerprint treats these as identity, not capacity)
        fp.update(self._layout.fingerprint_extra(self))
        if self._planes > 1:
            # an entry's head axis is planes x heads: a peer that reads
            # it as one plane of more heads is another model
            fp["cache_planes"] = self._planes
        return fp

    def _shared_block_count(self) -> int:
        """Blocks currently referenced beyond their first owner — the
        live HBM the prefix index is saving (0 for dense pools)."""
        if not self._layout.paged:
            return 0
        return sum(r - 1 for r in self._block_refs.values() if r > 1)

    def reset_prefix_stats(self) -> None:
        """Zero the cumulative hit/query/chunk counters: a caller that
        warms the pool calls this so the hit rate covers only the
        traffic after it (the warm request is an admission query that
        can never hit)."""
        self._prefix_queries = self._prefix_hits = 0
        self._prefix_tokens_matched = self._prefix_blocks_matched = 0
        self._chunks_total = self._chunk_tokens_total = 0

    def prefix_stats(self) -> dict:
        """Host-side prefix-sharing / chunked-prefill accounting: the
        quantities the serving gauges (``serving_prefix_hit_rate``,
        ``serving_prefix_blocks_shared``,
        ``serving_prefill_chunks_total``) read.
        Queries/hits are cumulative over admissions;
        ``blocks_shared_now`` is the live count of references beyond
        each block's first owner (HBM being saved right now)."""
        q = self._prefix_queries
        return {
            "enabled": self.prefix_sharing,
            "queries": q,
            "hits": self._prefix_hits,
            "hit_rate": (self._prefix_hits / q) if q else 0.0,
            "tokens_matched": self._prefix_tokens_matched,
            "blocks_matched": self._prefix_blocks_matched,
            "blocks_shared_now": self._shared_block_count(),
            "indexed_blocks": len(self._prefix_index),
            "prefill_chunk_tokens": self._chunk_tokens,
            "prefill_chunks_total": self._chunks_total,
            "prefill_chunk_tokens_total": self._chunk_tokens_total,
        }

    def prefix_digest(self, since_epoch: Optional[int] = None
                      ) -> Optional[dict]:
        """Cheap resident-prefix digest for affinity routing: the
        chain-hash keys currently in the prefix index, stamped with
        ``_prefix_epoch`` so a router can cache the key set and refresh
        only when the allocator/index actually changed.  Pass the
        epoch of the cached digest as ``since_epoch``: an unchanged
        index returns the epoch WITHOUT the key set (nothing to
        recopy); a changed one (or ``since_epoch=None``) includes
        ``"keys"``.  The keys are the same chained hashes
        ``_match_prefix`` walks, so a router replaying the chain over a
        prompt's head blocks predicts exactly which engine would hit.
        ``None`` when prefix sharing is off (dense layout) — the router
        then has no affinity signal and falls back to load placement."""
        if not self.prefix_sharing:
            return None
        d = {"epoch": self._prefix_epoch,
             "block_size": self._block_size,
             "indexed_blocks": len(self._prefix_index)}
        if since_epoch is None or since_epoch != self._prefix_epoch:
            d["keys"] = frozenset(self._prefix_index)
        return d

    def _on_activated(self, slot: int, rid, ids) -> None:
        """Subclass hook: a slot just became ACTIVE with its first
        token committed (fires for both the bucketed one-shot prefill
        and the chunked path's final chunk).  The speculative pool uses
        it to prefill its draft twin."""

    def _activate(self, slot: int, rid, ids, first,
                  max_new_tokens: int, priority: int = 0, tenant=None,
                  deadline=None, seq: int = 0, sampling=None,
                  adapter: int = 0) -> None:
        """Promote a slot to decoding: its prompt is fully resident and
        ``first``, the token sampled at the last prompt position, is
        still ON THE DEVICE.  It joins the step's carry there and rides
        the next download, where ``_commit_first`` commits it: no host
        sync here, which would wait out the step in flight and the
        prefill with the device idle behind them.  One code path for
        both prefill modes, so the hook order (``on_admit`` at
        slot-take, then ``_on_activated``, then ``on_token``) cannot
        diverge between them."""
        state = _SlotState(
            rid, ids, [], max_new_tokens, priority=priority,
            tenant=tenant, deadline=deadline, seq=seq,
            sampling=sampling, adapter=adapter)
        state.ahead = 1
        self._active[slot] = state
        self._firsts.append((slot, state, first))
        self._patch_carry(slot, first, state)
        if max_new_tokens > 1 and not self._prefill_only:
            # a slot that ends on its budget's one token never decodes
            # (and a prefill tier never does), so the subclass hook
            # (the speculative pool's draft prefill + splice) would be
            # pure wasted device work
            self._on_activated(slot, rid, ids)

    def _patch_carry(self, slot: int, tok, state: _SlotState) -> None:
        """Give ``slot``'s row of the device-resident carry the token
        its next step consumes and its draw counter (see ``_patch``)."""
        if self._tok_dev is None:
            self._tok_dev = self._place(np.zeros(self.slots, np.int32))
            self._step_dev = self._place(np.zeros(self.slots, np.uint32))
        cfg = state.sampling
        step = (0 if cfg is None else cfg.draws) \
            + len(state.tokens) + state.ahead
        self._tok_dev, self._step_dev = self._patch_jit(
            self._tok_dev, self._step_dev, np.int32(slot), tok,
            np.uint32(step))

    def _place(self, arrs):
        """Upload ``[slots]`` step vectors, one or a list of them in ONE
        transfer call; under a mesh committed to their dp sharding up
        front: an uncommitted input would let the compiled executable
        pick (and pay a reshard per call)."""
        if self._mesh is not None:
            return self._mesh.place(arrs, "dp")
        return jax.device_put(arrs)

    def _commit_first(self, slot: int, state: _SlotState,
                      first: int) -> None:
        """Commit a prefill's first token, downloaded with the step
        before its row's first.  On a prefill tier (docs §5n) a request
        that survives it PARKS instead of decoding: its prompt is fully
        resident and its first token committed, exactly the state
        ``export_kv()`` hands off.  One that finishes on its first
        token never hands off: it completes here like any other."""
        state.ahead -= 1
        if self._prefill_only and state.remaining > 1 \
                and first != self.eos_id:
            state.tokens.append(first)
            state.remaining -= 1
            self._prefill_done[state.rid] = (slot, self._active.pop(slot))
            self._out.append((state.rid, first, None))
            # handed on before the park is announced, as it always was
            self._hand_on()
            if self.on_prefill_done is not None:
                self.on_prefill_done(state.rid)
            return
        self._commit(slot, (first,))

    def _match_prefix(self, ids, shard: int = 0):
        """Longest resident block-aligned prefix of ``ids`` in the
        prefix index: ``(physical_blocks, matched_tokens,
        last_matched_chain_key)``.

        Block-granular by design: only FULL blocks are ever indexed, a
        full block is never written again (writes advance
        monotonically), so a matched block is immutable — the
        copy-on-write rule degenerates to never-write-shared.  The walk
        is chained (each key hashes the parent's key with the block's
        token ids) and each hit is verified token-equal against the
        entry, so a hash collision cannot splice another prompt's K/V.
        The FINAL prompt position is never matched — the request's
        first output token is sampled from the logits there, so at
        least one suffix token always runs through the chunk path.

        ``shard`` restricts the match to physical blocks in that dp
        shard's partition (a slot's table row may only name blocks of
        its own shard); an entry whose copies all live elsewhere ends
        the chain — with dp == 1 every block qualifies, the legacy
        behavior."""
        bs = self._block_size
        limit = (len(ids) - 1) // bs
        blocks: List[int] = []
        key = None
        last_matched = None
        for j in range(limit):
            toks = tuple(int(t) for t in ids[j * bs:(j + 1) * bs])
            parent, key = key, hash((key, toks))
            entry = self._prefix_index.get(key)
            if entry is None or entry.tokens != toks \
                    or entry.parent_key != parent:
                break
            if self._dp == 1:
                cand = entry.blocks[-1]
            else:
                cand = next((b for b in reversed(entry.blocks)
                             if self._shard_of_block(b) == shard), None)
                if cand is None:
                    break
            blocks.append(cand)
            last_matched = key
        return blocks, len(blocks) * bs, last_matched

    def _index_full_blocks(self, slot: int, st: _PrefillState) -> None:
        """Advance the slot's incremental prefix indexing: every PROMPT
        block whose last position is now written (``pos`` passed its
        end) becomes immutable and enters the index — so a hot shared
        prefix is matchable while its first owner is still prefilling
        the tail, not only after it activates.  Generated-token blocks
        are deliberately never indexed: the shareable traffic shape is
        common system prompts / few-shot prefixes, which live in the
        prompt."""
        bs = self._block_size
        blocks = self._slot_blocks.get(slot)
        if blocks is None:
            return
        if (st.indexed + 1) * bs <= st.pos:
            self._prefix_epoch += 1
        while (st.indexed + 1) * bs <= st.pos:
            j = st.indexed
            toks = tuple(int(t) for t in st.ids[j * bs:(j + 1) * bs])
            key = hash((st.chain_key, toks))
            entry = self._prefix_index.get(key)
            if entry is None:
                self._prefix_index[key] = _PrefixEntry(
                    blocks[j], toks, st.chain_key)
                self._block_keys[blocks[j]] = key
            elif entry.tokens == toks \
                    and entry.parent_key == st.chain_key:
                # same content already indexed: a concurrent duplicate
                # prompt computed its own bit-identical copy — list it,
                # so the chain survives whichever owner frees first
                if blocks[j] not in entry.blocks:
                    entry.blocks.append(blocks[j])
                    self._block_keys[blocks[j]] = key
            else:
                # hash COLLISION with a different chain: listing this
                # block under the entry would let _match_prefix serve
                # its K/V against the entry's verified tokens — the
                # exact splice the collision guard exists to prevent.
                # The chain is unmatchable past this link either way
                # (lookups re-verify tokens+parent), so stop indexing
                # this slot's prompt entirely
                st.indexed = len(st.ids) // bs
                return
            st.chain_key = key
            st.indexed += 1

    def _admit_chunked(self, req: _Request, need: int, matched_blocks,
                       matched_len: int, chain_key,
                       shard: int = 0) -> None:
        """Chunked-prefill admission: map the matched prefix blocks
        READ-ONLY (refcounts bumped), allocate fresh blocks for
        everything from ``matched_len`` on (suffix + generation — every
        position this request will WRITE), point the slot's table row
        at them and set its index to ``matched_len``.  No prompt
        forward runs here: ``_chunk_work`` processes the unmatched
        suffix at most ``prefill_chunk_tokens`` per tick.  ``shard``
        (chosen by ``_choose_shard``) pins the slot and every block to
        one dp partition."""
        _fire("pool.alloc_blocks")
        slot = self._pop_free_slot(shard)
        for b in matched_blocks:
            self._block_refs[b] += 1
        blocks = list(matched_blocks) + \
            self._alloc_blocks(need - len(matched_blocks), shard)
        self._slot_blocks[slot] = blocks
        padded = np.full(self._max_blocks, self._shard_scratch(shard),
                         np.int32)
        padded[:len(blocks)] = blocks
        self._cache = self._admit_jit(
            self._cache, jnp.asarray(slot, jnp.int32),
            jnp.asarray(padded), jnp.asarray(matched_len, jnp.int32))
        self._prefilling[slot] = _PrefillState(
            req.rid, req.ids, matched_len, req.max_new_tokens,
            matched_blocks=len(matched_blocks), chain_key=chain_key,
            priority=req.priority, tenant=req.tenant,
            deadline=req.deadline, seq=req.seq, sampling=req.sampling,
            adapter=req.adapter)
        if self.prefix_sharing:
            self._prefix_queries += 1
            if matched_len:
                self._prefix_hits += 1
                self._prefix_tokens_matched += matched_len
                self._prefix_blocks_matched += len(matched_blocks)
            self.last_admit_prefix_tokens = matched_len
        else:
            self.last_admit_prefix_tokens = None
        if self.on_admit is not None:
            self.on_admit(req.rid, slot, len(req.ids))

    def _tenant_counts(self) -> Optional[Dict]:
        """Live slots per tenant (active + prefilling), None when no
        fairness cap is configured."""
        if self._tenant_cap is None:
            return None
        counts: Dict = {}
        for st in list(self._active.values()) \
                + list(self._prefilling.values()):
            if st.tenant is not None:
                counts[st.tenant] = counts.get(st.tenant, 0) + 1
        return counts

    def tenant_at_cap(self, tenant) -> bool:
        """True when ``tenant`` currently holds its full fairness-cap
        share of slots — ``_pick_candidate`` would defer its queued
        requests right now.  The engine's preempt rung uses this to
        avoid evicting a victim for a request the refill cannot admit
        anyway (always False without a cap or for tenant-less
        requests)."""
        if self._tenant_cap is None or tenant is None:
            return False
        counts = self._tenant_counts()
        return counts.get(tenant, 0) >= self._tenant_cap

    def _pick_candidate(self, tenants):
        """The next request a free slot should serve: queued admissions
        and parked (preempted) resumes compete in ONE ordering —
        ``(priority desc, deadline asc, arrival asc)`` — so a spilled
        high-priority request outranks a cold low-priority one and vice
        versa, and deadline-aware slot selection falls out of the same
        comparison.  Tenants at their fairness cap are skipped (a slot
        freeing later lifts the cap — never starvation, just deferral).
        Returns ``("queued", _Request) | ("resume", _SpillState) |
        None``."""
        best = best_key = None
        inf = float("inf")
        for req in self._queue:
            if tenants is not None and req.tenant is not None \
                    and tenants.get(req.tenant, 0) >= self._tenant_cap:
                continue
            key = (-req.priority,
                   inf if req.deadline is None else req.deadline,
                   req.seq)
            if best_key is None or key < best_key:
                best, best_key = ("queued", req), key
        for sp in self._spilled.values():
            if tenants is not None and sp.tenant is not None \
                    and tenants.get(sp.tenant, 0) >= self._tenant_cap:
                continue
            key = (-sp.priority,
                   inf if sp.deadline is None else sp.deadline,
                   sp.seq)
            if best_key is None or key < best_key:
                best, best_key = ("resume", sp), key
        return best

    def _match_prefix_memo(self, req: _Request, shard: int):
        """Per-(candidate, epoch, shard) memo over ``_match_prefix``:
        a blocked head would otherwise re-walk its whole chain (tuple-
        build + hash per block) every tick per shard until blocks
        free.  The epoch bumps on any allocator/index mutation, so a
        memoized match is exactly as fresh as a recomputed one."""
        sig = (req.rid, self._prefix_epoch)
        if self._head_match is None or self._head_match[0] != sig:
            self._head_match = (sig, {})
        per_shard = self._head_match[1]
        if shard not in per_shard:
            per_shard[shard] = self._match_prefix(req.ids, shard)
        return per_shard[shard]

    def _choose_shard(self, req: _Request, need: int):
        """Pick the dp shard a queued paged admission should land in:
        among shards with a free slot, the one whose partition can
        hold the reservation (free + reclaimable-spilled, minus any
        prefix hit), preferring the LONGEST prefix match and then the
        most headroom.  Returns ``(shard, matched_blocks, matched_len,
        chain_key)`` — ``(None, [], 0, None)`` when no shard with a
        free slot can hold it right now (the caller block-waits).
        With dp == 1 this reduces exactly to the legacy single-list
        admission check."""
        shards = sorted({self._shard_of_slot(s) for s in self._free})
        best = best_key = None
        for s in shards:
            matched: tuple = ([], 0, None)
            if self.prefix_sharing:
                matched = self._match_prefix_memo(req, s)
            avail = len(self._free_by_shard[s]) \
                + self._spilled_dev_count(s)
            if need - len(matched[0]) > avail:
                continue
            key = (matched[1], avail)
            if best_key is None or key > best_key:
                best, best_key = (s,) + matched, key
        if best is None:
            return None, [], 0, None
        return best

    def _refill(self):
        tr = _trace_active()
        self.admission_blocked = False
        spliced = None
        while (self._queue or self._spilled) and self._free:
            pick = self._pick_candidate(self._tenant_counts())
            if pick is None:
                break  # every candidate is tenant-capped right now
            kind, item = pick
            if kind == "resume":
                if not self._layout.paged:
                    # an O(1) carry holds no device blocks and is not
                    # shard-pinned (its restorable copy is host/disk
                    # bytes): any free slot resumes it, and the while
                    # condition already guarantees one
                    self._spilled.pop(item.rid)
                    self._resume(item)
                    continue
                # a resume is SHARD-PINNED: its zero-copy device blocks
                # and its table row's partition live in the shard it
                # was preempted from — block-wait for a slot there
                if self._dp > 1 and not any(
                        self._shard_of_slot(s) == item.shard
                        for s in self._free):
                    self.admission_blocked = True
                    break
                # re-acquire the fresh blocks the resume needs (blocks
                # still in the spill tier re-map for free; the tier's
                # OTHER entries in the same shard are reclaimable on
                # top of its free list)
                own = sum(1 for b in item.dev_blocks if b is not None)
                need_fresh = item.total_blocks - own
                avail = len(self._free_by_shard[item.shard]) \
                    + self._spilled_dev_count(item.shard) - own
                if need_fresh > avail:
                    self.admission_blocked = True
                    break  # block-wait on the CHOSEN candidate
                self._spilled.pop(item.rid)
                self._resume(item)
                continue
            req = item
            matched_blocks, matched_len, chain_key = [], 0, None
            shard = None
            if self._layout.paged:
                # admission control: the chosen candidate waits until
                # enough blocks are free (+reclaimable from the spill
                # tier) for its whole reservation IN SOME SHARD with a
                # free slot — skipping ahead to a smaller request would
                # starve long prompts within the declared priority
                # ordering.  With sharing, matched blocks come off the
                # requirement: a hit admits under block pressure a cold
                # prompt could not
                need = self._blocks_needed(len(req.ids),
                                           req.max_new_tokens)
                shard, matched_blocks, matched_len, chain_key = \
                    self._choose_shard(req, need)
                if shard is None:
                    self.admission_blocked = True
                    break
            # remove by IDENTITY: _Request is a namedtuple holding a
            # numpy array — value equality would compare prompt arrays
            # element-wise the moment two rids ever collided
            for i, q in enumerate(self._queue):
                if q is req:
                    del self._queue[i]
                    break
            if self._chunk_tokens is not None:
                self._admit_chunked(req, need, matched_blocks,
                                    matched_len, chain_key, shard)
                continue
            # the batch-1 prefill runs BEFORE the slot is popped so a
            # prefill failure can never leak a slot.  ``bucket``: the
            # length the prompt is padded to, so 1 - prompt_tokens /
            # bucket is the prefill's padding
            _fire("pool.prefill")
            if spliced is not None:
                # a burst throttles itself on the device: a FURTHER
                # prefill of this phase is dispatched when the one before
                # it is done, so that two row caches are alive at most
                # (each a whole row's K/V: 0.4 GB at gpt-1.3b's widths),
                # where nothing else bounds what a burst of admissions
                # has in flight.  The first never waits, so a steady
                # tick, which admits one, makes no sync; and where one
                # waits the device has the splice before it to run
                # while the next prefill is dispatched
                jax.block_until_ready(spliced)
            with tick_phase(tr, "tick.prefill",
                            lambda: self._prefill_meta(req)):
                row_cache, length, tok = self._prefill_row(req)
            spliced = row_cache
            slot = self._pop_free_slot(shard)
            args = (self._cache, row_cache, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(length, jnp.int32))
            if self._layout.paged:
                _fire("pool.alloc_blocks")
                blocks = self._alloc_blocks(need, shard)
                self._slot_blocks[slot] = blocks
                # pad the table row to max_blocks with the shard's
                # scratch block: unreserved logical blocks are never
                # read (masked past the request's span) and their
                # splice writes are trash
                padded = np.full(self._max_blocks,
                                 self._shard_scratch(shard), np.int32)
                padded[:need] = blocks
                args += (jnp.asarray(padded),)
            self._cache = self._insert_jit(*args)
            if self._ring is not None:
                # the prompt's blocks that lie behind the ring were never
                # copied: as many ring entries lapped
                self.window_blocks_overwritten += max(
                    0, (length - 1) // self._block_size + 1 - self._ring[1])
            self.last_admit_prefix_tokens = None
            if self.on_admit is not None:
                self.on_admit(req.rid, slot, len(req.ids))
            self._start_slot(slot, req, tok)

    def _prefill_row(self, req: _Request):
        """Dispatch the batch-1 prefill of an admitted request:
        ``(row_cache, length, tok)``, the cache to splice into its slot,
        the index the slot starts at, and the device handle of what
        ``_start_slot`` needs from the prefill.  Here the bucketed
        prefill (compiled per bucket, shared with
        ``DecodeSession.generate``), whose ``tok`` is the request's
        FIRST token."""
        # the request's resolved config rides the batch-1 prefill as
        # a [1] SamplingState (prefill draw = stream step 0); the
        # advanced state it returns is discarded — the slot's draw
        # counter is derived from its tokens when it joins the carry
        samp = make_sampling_state(
            1, temperature=req.sampling.temperature,
            top_k=req.sampling.top_k, top_p=req.sampling.top_p,
            seed=req.sampling.seed, step=req.sampling.draws,
            adapter=req.adapter)
        row_cache, tok, _ = self._session.prefill(req.ids[None], samp)
        return row_cache, len(req.ids), tok

    def _start_slot(self, slot: int, req: _Request, tok) -> None:
        """Make ``slot`` live for the request just spliced into it; its
        first token stays on the device (``_activate``)."""
        self._activate(slot, req.rid, req.ids, tok,
                       req.max_new_tokens, priority=req.priority,
                       tenant=req.tenant, deadline=req.deadline,
                       seq=req.seq, sampling=req.sampling,
                       adapter=req.adapter)

    def _chunk_work(self, tr) -> None:
        """At most ``prefill_chunk_tokens`` of prompt work this tick:
        ONE padded ``[C]`` chunk call advancing the OLDEST prefilling
        slot (FIFO — concurrent admissions' prompts serialize, each
        tick still runs the batched decode step for every active slot).
        The final chunk's sampled token activates the slot."""
        if not self._prefilling:
            return
        slot = next(iter(self._prefilling))
        st = self._prefilling[slot]
        n = min(self._chunk_tokens, len(st.ids) - st.pos)
        toks = np.zeros(self._chunk_tokens, np.int32)
        toks[:n] = st.ids[st.pos:st.pos + n]
        params, bufs = self._weights()
        _fire("pool.prefill")
        # the request's resolved config as [1] vectors; every chunk
        # passes the same (seed, step 0) stream, so only the FINAL
        # chunk's kept sample matters and it matches the bucketed path
        samp = self._samp_vec(st.sampling)
        adpt = jnp.asarray([st.adapter], jnp.int32)
        with tick_phase(tr, "tick.prefill", lambda: {
                "rid": st.rid, "chunk_tokens": n, "pos": st.pos,
                "prompt_tokens": len(st.ids),
                "bucket": self._chunk_tokens}):
            self._cache, tok_dev = self._chunk_jit(
                params, bufs, self._cache, jnp.asarray(toks),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(st.pos, jnp.int32),
                jnp.asarray(n, jnp.int32), samp, adpt)
        self._chunks_total += 1
        self._chunk_tokens_total += n
        st.pos += n
        if self.prefix_sharing:
            # blocks this chunk completed are immutable now: index them
            # immediately, so a queued request sharing this prefix can
            # match it at ITS admission, mid-prefill
            self._index_full_blocks(slot, st)
        if st.pos < len(st.ids):
            return
        # prompt fully resident: the chunk's sample IS the first token
        # (intermediate chunks' samples are never fetched)
        self._prefilling.pop(slot)
        self._activate(slot, st.rid, st.ids, tok_dev, st.max_new_tokens,
                       priority=st.priority, tenant=st.tenant,
                       deadline=st.deadline, seq=st.seq,
                       sampling=st.sampling, adapter=st.adapter)

    def _sync_step_inputs(self):
        """The host work before a launch (the speculative pool adds its
        draft's weights to it): upload what the host knows of the rows
        the step takes (``_rows``) when they are not the last launch's,
        and lazily cache the weight value lists.  Returns ``_launch``'s
        arguments, here ``(params, bufs)``.

        Uploaded WHOLE, on a change of ``_live_sig``: the active mask
        and the per-slot AS-DATA vectors (docs §5q), the sampling config
        stack ``_samp_dev`` = (temperature, top_k, top_p, seed) and the
        adapter ids ``_adapter_dev``.  A row the step does not take is
        greedy/base (temp 0, adapter 0) — its output is discarded
        anyway, and greedy is the cheapest row.  The token vector and
        the draw counter are NOT uploaded: they are the step's carry,
        patched on the device where a row joins (``_patch_carry``)."""
        sig = tuple((slot, st.seq) for slot, st in self._rows)
        if sig != self._live_sig:
            active = np.zeros(self.slots, bool)
            temp = np.zeros(self.slots, np.float32)
            tk = np.zeros(self.slots, np.int32)
            tp = np.ones(self.slots, np.float32)
            seed = np.zeros(self.slots, np.uint32)
            adpt = np.zeros(self.slots, np.int32)
            for slot, st in self._rows:
                active[slot] = True
                cfg = st.sampling
                if cfg is not None:
                    temp[slot] = cfg.temperature
                    tk[slot] = cfg.top_k
                    tp[slot] = cfg.top_p
                    seed[slot] = cfg.seed & 0xFFFFFFFF
                adpt[slot] = st.adapter
            # one packed upload for a changed row set, not six
            self._active_dev, *samp, self._adapter_dev = self._place(
                [active, temp, tk, tp, seed, adpt])
            self._samp_dev = tuple(samp)
            self._draws = bool((temp > 0).any())
            self._live_sig = sig
        return self._weights()

    def _weights(self):
        """The parameter and buffer value lists, walked once and kept
        (``refresh_weights`` drops them)."""
        if self._state_cache is None:
            self._state_cache = self._session._state_vals()
        return self._state_cache

    # how many steps may be in flight when ``step`` returns: 1 where the
    # step's carry lives on the device, so that the next launch needs
    # nothing of the host (this pool, the block pool); 0 where a launch
    # reads what the last download brought.  A property of the KIND of
    # pool, read by the skeleton below: no option sets it
    _depth = 1
    # rows of a layer's input that a slot takes in the compiled step (the
    # block pool: a block's positions)
    _rows_a_slot = 1
    # the step that committed the token being handed on, where a pool
    # commits a token some steps after it was first computed (the block
    # pool's ``_leaving`` sets it a token); it rides beside each token
    # of the batch
    token_commit_step: Optional[int] = None

    def step(self) -> bool:
        """One tick, the same for every kind of pool, with the host one
        step BEHIND the device (docs §5t): launch step t+1 from the
        carry on the device, download step t (the tick's ONE
        ``device_get``, which returns while t+1 runs) and commit its
        tokens, refill the slots that freed (prefill and splice are
        dispatched behind t+1; the new row joins step t+2), and return
        with exactly one step in flight, so that whatever the caller
        does between two ticks runs under it too.  One call delivers one
        step's tokens: a call that finds nothing in flight refills
        first and launches twice.  False when the pool is drained
        (``_pending()``).

        A kind of pool differs in its hooks and in ``_depth``, never in
        this skeleton: ``_launchable`` (may this row take another
        step), ``_sync_step_inputs`` (host work before the launch; what
        it returns are the launch's arguments), ``_launch`` (the step's
        dispatches, cache donated and rebound, returning the device
        arrays the host needs), ``_deliver`` (the downloaded arrays
        into ``_commit``, 0..n tokens a slot) and ``_decode_meta``.
        The hooks find the rows their step is about in ``_rows``.

        With a tracer installed (serving/trace.py) each phase is a span
        — admit (refill incl. per-request prefill), prep (the launch's
        host work: the list of rows that take the step,
        ``_sync_step_inputs`` and ``tick.decode``'s meta; its own meta
        says how many ``rows`` it found, 0 where it launched nothing,
        and whether their vectors were ``uploaded``, 1, or the last
        launch's stood, 0), decode (``_launch`` alone: the
        dispatches, which return before the device has finished; its
        meta says how many of the ``slots`` rows were ``live`` and
        whether the launch was made ``ahead`` of a step in flight),
        sample (the host download, where the host waits for the
        device), deliver (the host loop committing tokens and firing
        hooks; its meta counts the step's ``rows`` and those ``ended``
        before it was launched) — and without one ``tick_phase`` is a
        shared no-op."""
        _fire("pool.step")
        tr = _trace_active()
        idle = not self._flights
        if idle:
            self._admit_phase(tr)
            self._launch_step(tr)
        if self._flights and self._depth:
            self._launch_step(tr)
        self._settle_one(tr)
        if self._flights:
            if not idle:
                self._admit_phase(tr)
            if not any(self._active.get(slot) is st
                       for slot, st in self._flights[0][1]):
                # every row of it has ended: dropped, never awaited
                self._flights.clear()
        return self._pending()

    def _admit_phase(self, tr) -> None:
        """Refill free slots, then the bounded prompt work of a chunking
        pool; once a tick, under the step in flight where there is one."""
        with tick_phase(tr, "tick.admit"):
            self._refill()
        if self._chunk_tokens is not None:
            self._chunk_work(tr)

    def _launchable(self, slot: int, state) -> bool:
        """Whether ``state`` takes (another) step: not on a prefill
        tier, and not once its budget's last token is in flight — a
        budget end is known at launch, an EOS is not."""
        return state.ahead < state.remaining and not self._prefill_only

    def _launch_step(self, tr) -> None:
        """Launch ONE step for every launchable row, if there is one,
        and put it in flight.  ``tick.prep`` is everything before the
        dispatch, ``tick.decode`` the dispatch; the decode span is made
        inside the prep with its meta, so without a tracer neither the
        meta nor a span is built."""
        with tick_phase(tr, "tick.prep") as prep:
            rows = [(slot, st) for slot, st in self._active.items()
                    if self._launchable(slot, st)]
            if not rows:
                if prep is not None:
                    prep.set(rows=0, uploaded=0)
                return
            self._rows = rows
            sig = self._live_sig
            inputs = self._sync_step_inputs()
            decode = _NO_SPAN
            if prep is not None:
                # a row set that changed was uploaded under a NEW signature
                prep.set(rows=len(rows),
                         uploaded=int(self._live_sig is not sig))
                decode = tr.span("tick.decode",
                                 **self._decode_meta(*inputs),
                                 ahead=len(self._flights))
        with decode:
            handles = self._launch(*inputs)
        self._flights.append((handles, rows))

    def _settle_one(self, tr) -> None:
        """Download the oldest step in flight, and every prefill's first
        token with it, in ONE batched transfer (the designed sync point
        whether or not it is spanned), and commit: the first tokens,
        then the step's for the rows that have not ended since it was
        launched (an EOS at step t is seen only after t+1 was launched:
        that row computed once more inside its own reservation, and its
        download is discarded)."""
        if not (self._flights or self._firsts):
            return
        handles, rows = self._flights.popleft() if self._flights \
            else (None, ())
        firsts, self._firsts = self._firsts, []
        with tick_phase(tr, "tick.sample"):
            host, first_toks = jax.device_get(
                (handles, [tok for _, _, tok in firsts]))
        with tick_phase(tr, "tick.deliver") as span:
            self._hook_calls = 0
            for (slot, st, _), tok in zip(firsts, first_toks):
                self._commit_first(slot, st, int(tok.reshape(-1)[0]))
            if self._ending:
                # a request that ended on its first token leaves its
                # slot before the step's rows are read: what the step
                # computed for it is discarded
                self._hand_on()
            self._rows = [(slot, st) for slot, st in rows
                          if self._active.get(slot) is st]
            if handles is not None:
                self._deliver(host)
            self._hand_on()
            if span is not None:
                # known only here: set on the span as the engine's
                # ``tick`` span sets what the tick did; ``hook_calls``
                # the calls into the token hooks (1 where a batch hook
                # took the download's tokens whole)
                span.set(rows=len(rows), ended=len(rows) - len(self._rows),
                         hook_calls=self._hook_calls)

    def _settle(self) -> None:
        """Bring the host level with the device: download and deliver
        whatever is in flight.  Called by everything that reads or moves
        a slot's device state outside the tick (cancel, preempt and the
        spill path, ``export_kv``, ``release``, a weight or adapter
        swap): rare, and none runs in a steady state."""
        while self._flights or self._firsts:
            self._settle_one(_trace_active())

    def _pending(self) -> bool:
        """Whether any request is still anywhere in the pool (a step in
        flight counts through its live rows, which are in ``_active``;
        one whose rows have all ended was dropped)."""
        return bool(self._active or self._queue or self._prefilling
                    or self._spilled or self._prefill_done)

    def _last_position(self, slot: int, state) -> int:
        """The last position the step being launched lets ``slot`` see:
        the one it writes."""
        return len(state.ids) + len(state.tokens) + state.ahead - 1

    def _prefill_meta(self, req) -> dict:
        """``tick.prefill``'s meta for a bucketed prefill; ``chunks`` where
        the model's prefill is a scan over chunks of the bucket (a
        recurrent state's: ``models.PowerRetentionLM.prefill_chunks``)."""
        bucket = self._session._bucket_for(len(req.ids))
        meta = {"rid": req.rid, "prompt_tokens": len(req.ids),
                "bucket": bucket}
        chunks = getattr(self._session._model, "prefill_chunks", None)
        if chunks is not None:
            meta["chunks"] = chunks(bucket)
        return meta

    def _block_meta(self) -> dict:
        """``tick.decode``'s meta, each figure over its own entries: where
        entries are paged (``kv_entries`` of them, ``kv_planes`` K/V
        planes in all: an entry of a stack run several times holds one a
        pass, and ``passes`` says how many) ``live_blocks``, the
        table entries the live slots' positions reach, counted ONCE a
        position whatever the planes (what one attention call fetches and
        computes: ``ops/pallas_decode.py`` skips the rest), and
        ``table_blocks``, slots x table width;
        where entries are recurrent (``state_entries``) ``state_bytes``;
        ``latent_entries`` where the paged entries hold latents.
        A model that mixes kinds carries both in the one span, and the
        counts of ENTRIES that say what each figure is over (a layer may
        own an entry of each kind)."""
        meta = {}
        if self._layout.recurrent:
            # a recurrent state: what the step reads AND writes of it,
            # from shapes (live rows x the bytes a slot's state takes)
            meta["state_bytes"] = len(self._rows) * self._state_bytes_slot
        if self._layout.paged:
            bs = self._block_size
            meta.update(
                live_blocks=sum(self._last_position(slot, st) // bs + 1
                                for slot, st in self._rows),
                table_blocks=self.slots * self._max_blocks,
                **self._entries_meta)
            if self._ring is not None:
                # the ring entries ONE windowed call walks, over the live
                # slots: from the entry of the band's first position to
                # the entry of the last, never more than the ring
                window, ring = self._ring
                meta["window_live_blocks"] = sum(
                    min(top // bs - max(top - window + 1, 0) // bs + 1,
                        ring)
                    for top in (self._last_position(slot, st)
                                for slot, st in self._rows))
        if "latent" in self._by_kind:
            # the paged figures above run over latent entries: a block is
            # one latent a position, not K/V by head
            meta["latent_entries"] = self._by_kind["latent"][0]
        if len(self._by_kind) > 1 and "recurrent" in self._by_kind:
            meta["state_entries"] = self._by_kind["recurrent"][0]
        return meta

    def _expert_meta(self, live: int) -> dict:
        """What a step with ``live`` live slots reads of the model's routed
        expert layers, from shapes (nothing is asked of the device):
        ``moe_route``, the route the step's rows compile the layers to
        (``F.expert_route``); ``experts_held``, the experts held, summed
        over the layers; ``experts_read_expected``, those whose weights
        the step is expected to read: all of them where every expert runs
        on every row, else the share that the live rows' even choices
        touch (``F.touched_share``).  An inactive slot's row is routed
        too, so a sparse pool reads more than this.  Empty for a model
        with no such layer."""
        if not self._experts:
            return {}
        rows = live * self._rows_a_slot
        return dict(
            moe_route=self._expert_route,
            experts_held=self._experts_held,
            experts_read_expected=sum(
                held * (1.0 if self._expert_route == "every"
                        else F.touched_share(rows, n, k))
                for held, n, k in self._experts))

    def _decode_meta(self, *inputs) -> dict:
        """``tick.decode``'s meta, from what ``_launch`` is about to be
        given; built only under a tracer."""
        live = len(self._rows)
        return dict(live=live, slots=self.slots,
                    greedy=int(not self._draws), **self._block_meta(),
                    **self._expert_meta(live))

    def _launch(self, params, bufs):
        """The one batched decode dispatch (cache donated and rebound in
        the same statement).  The token vector and the draw counter feed
        straight back on-device — active rows advanced inside the
        step."""
        self._cache, self._tok_dev, self._step_dev = self._decode_jit(
            params, bufs, self._cache, self._tok_dev, self._active_dev,
            self._samp_dev, self._step_dev, self._adapter_dev)
        if self._ring is not None:
            # a row whose step writes the first position of a block at or
            # past the ring's length laps one ring entry
            bs, ring = self._block_size, self._ring[1]
            for slot, st in self._rows:
                top = self._last_position(slot, st)
                self.window_blocks_overwritten += \
                    top % bs == 0 and top // bs >= ring
        for _, st in self._rows:
            st.ahead += 1
        self.steps_drawing += self._draws
        self.loop_passes += self._planes
        return self._tok_dev

    def _deliver(self, tok) -> None:
        """Commit the step's sampled token to every row of it that is
        still live."""
        toks = tok.tolist()
        for slot, st in self._rows:
            st.ahead -= 1
            self._commit(slot, (toks[slot],))

    def _commit(self, slot: int, tokens) -> None:
        """Commit the tokens a step produced for ``slot``, in order and
        0..n of them: append, count the budget down, put the token with
        those ``_hand_on`` hands to the serving layer, and at EOS or at
        the end of the budget mark the slot as ending, leaving what
        comes after uncommitted.  ``tokens`` is iterated one commit at a
        time, so a pool may pass a generator that does its own
        bookkeeping before each token leaves."""
        state = self._active[slot]
        rid, out = state.rid, self._out
        for t in tokens:
            state.tokens.append(t)
            state.remaining -= 1
            out.append((rid, t, self.token_commit_step))
            if state.remaining == 0 or t == self.eos_id:
                self._ending.append(slot)
                return

    def _hand_on(self) -> None:
        """Hand the tokens committed since the last call to the serving
        layer in ONE call of ``on_tokens``, then finish the slots whose
        request ended on one of them, in the order they ended: a
        request's ``on_finish`` follows its last token's delivery.  What
        the tick does for a download it does once, not once a row
        (docs §5t)."""
        if self._out:
            batch, self._out = self._out, []
            calls = self.on_tokens(batch)
            self._hook_calls += 1 if calls is None else calls
        if self._ending:
            ending, self._ending = self._ending, []
            for slot in ending:
                self._finish(slot)

    def _each_token(self, batch) -> int:
        """The batch hook a pool starts with: every token to
        ``on_token(rid, token)`` in turn, ``token_commit_step`` standing
        at the token's own while it runs.  Returns the calls made."""
        if self.on_token is None:
            return 0
        for rid, t, step in batch:
            self.token_commit_step = step
            self.on_token(rid, t)
        self.token_commit_step = None
        return len(batch)

    def refresh_weights(self):
        """Drop the cached parameter/buffer value lists — call after
        mutating the model's weights (e.g. ``set_state_dict``) so later
        decode steps see the new values.  The step in flight was
        launched with the old ones: it is delivered first, so every
        token after this call comes of the new."""
        _fire("weights.refresh")
        self._settle()
        self._state_cache = None

    # -- multi-LoRA hot-swap (nn.lora; docs §5q) -------------------------
    @property
    def lora_config(self):
        """``(n_adapters, rank)`` of the attached bank, or None."""
        return self._lora_cfg

    def load_adapter(self, idx: int, weights) -> None:
        """Write one adapter's weights into bank row ``idx`` and make
        the next tick serve it — a row-granular weight push: shapes are
        unchanged, so zero new compiles and an unchanged
        ``cost_version()`` (the hot-swap contract tests pin)."""
        _lora_mod.load_adapter(self._session._model, idx, weights)
        self.refresh_weights()

    def unload_adapter(self, idx: int) -> None:
        """Zero bank row ``idx`` back to the identity.  Refuses while
        any live request (queued, prefilling, active, parked or
        spilled) is pinned to it — an in-flight request would silently
        continue under the BASE model mid-stream."""
        cfg = self._lora_cfg
        if cfg is not None:
            idx_i = int(idx)
            live = [st.adapter for st in self._active.values()]
            live += [st.adapter for st in self._prefilling.values()]
            live += [sp.adapter for sp in self._spilled.values()]
            live += [st.adapter for _, st in self._prefill_done.values()]
            live += [rq.adapter for rq in self._queue]
            if idx_i in live:
                raise PreconditionNotMetError(
                    "adapter %d still has live requests pinned to it; "
                    "drain or cancel them before unloading — an "
                    "in-flight request would silently fall back to the "
                    "base model mid-stream" % idx_i)
        _lora_mod.unload_adapter(self._session._model, idx)
        self.refresh_weights()

    def reset(self):
        """Discard every request and all cache/allocator state — queue,
        slots, results, paged free list, the K/V arrays themselves —
        while KEEPING the compiled executables and the cached weight
        value lists.  This is the serving engine's recovery primitive:
        after a failed step nothing pool-side can be trusted, but
        prompt + committed tokens fully determine greedy decode state
        (the O(1)-cache contract), so a rebuilt-empty pool plus
        re-prefilled resubmissions continues survivors
        token-identically at the cost of a cache re-allocation — never
        a recompile (``compile_counts()`` is unchanged, pinned by
        tests)."""
        self._queue.clear()
        self._active.clear()
        self._prefilling.clear()
        self._free = list(range(self.slots))
        self._alloc_version += 1
        # whatever is in flight is of the cache being discarded:
        # dropped, never awaited; the carry starts over with it
        self._flights.clear()
        self._firsts.clear()
        self._out.clear()
        self._ending.clear()
        self._tok_dev = None
        self._step_dev = None
        self._live_sig = None
        self._results.clear()
        self._finish_reasons.clear()
        self._used_rids.clear()
        # the spill tier names physical blocks of the cache being
        # discarded AND host copies of state the engine will resubmit
        # from its own records: both die with the pool (the engine's
        # recovery resubmits a preempted victim's prompt+committed like
        # any other survivor — byte-identical either way).  Disk-tier
        # files die too: stale K/V under a recurring rid would be worse
        # than no file (restore falls back to resubmit without one)
        for sp in self._spilled.values():
            self._spill_drop(sp)
        self._spilled.clear()
        self._spill_owner.clear()
        # parked prefill-complete requests name blocks of the cache
        # being discarded; the engine resubmits them like any survivor
        self._prefill_done.clear()
        self.admission_blocked = False
        if self._layout.paged:
            self._free_by_shard = [
                list(range(s * self._blocks_per_shard + 1,
                           (s + 1) * self._blocks_per_shard))
                for s in range(self._dp)]
            self._slot_blocks = {}
            self._block_refs = {}
            # the prefix index names physical blocks in the cache being
            # discarded: it MUST clear with them, or a post-recovery
            # admission would map freed-then-reused blocks as a "shared
            # prefix" and the rebuild-and-resubmit contract (byte-
            # identical survivors) would silently break
            self._prefix_index.clear()
            self._block_keys.clear()
            self._prefix_epoch += 1
            self._head_match = None
        self._cache = self._new_cache()

    def run(self) -> Dict[object, np.ndarray]:
        """Drain queue + slots; {request_id: np.int32 token array}."""
        while self.step():
            pass
        out, self._results = self._results, {}
        self._used_rids -= set(out)  # collected ids become reusable
        for rid in out:
            self._finish_reasons.pop(rid, None)
        return out

    def generate(self, prompts, max_new_tokens: int) -> List[np.ndarray]:
        """Convenience: submit all, drain, return in submission order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        results = self.run()
        return [results[r] for r in rids]

    def compile_counts(self) -> dict:
        counts = self._session.compile_counts()
        counts["pool_decode"] = int(self._decode_jit._cache_size())
        counts["slot_insert"] = int(self._insert_jit._cache_size())
        if self._chunk_jit is not None:
            # chunked prefill adds a FIXED pair: one [C] chunk shape +
            # one admission write — never a compile per prompt length
            # (the retrace-hazard contract, pinned by tests)
            counts["prefill_chunk"] = int(self._chunk_jit._cache_size())
            counts["slot_admit"] = int(self._admit_jit._cache_size())
        return counts

    def cost_version(self) -> int:
        """Total AOT compilations across the pool's executables — the
        cheap fingerprint the serving engine polls per tick so cost
        gauges refresh only when an executable actually changed."""
        version = (self._session.cost_version()
                   + self._decode_jit.compiles
                   + self._insert_jit.compiles)
        if self._chunk_jit is not None:
            version += self._chunk_jit.compiles + self._admit_jit.compiles
        return version

    def alloc_version(self) -> int:
        """The allocator's version: it moves with every slot take and
        release and every block allocation, free, share, spill and
        resume, and with nothing else.  A reservation is taken whole at
        admission, so between two of its values ``cache_stats()``
        returns what it returned: the serving engine polls it a tick
        and recomputes the cache gauges only when it moved (the
        ``cost_version()`` pattern)."""
        return self._alloc_version

    def _derived_costs(self, step_entry: Optional[dict],
                       tokens_per_step_per_slot: float = 1.0,
                       basis: str = "decode step advances every slot "
                                    "one token") -> dict:
        """The per-token derivation shared with the speculative pool:
        one batched step's compiler-reported FLOPs/bytes divided over
        the tokens it commits.  ``step_entry`` is the steady-state step
        executable's attribution (None before its first compile)."""
        if not step_entry or "flops" not in step_entry:
            return {}
        tokens = self.slots * float(tokens_per_step_per_slot)
        out = {
            "step_flops": step_entry["flops"],
            "step_bytes_accessed": step_entry["bytes_accessed"],
            "hbm_reserved_bytes": step_entry.get("hbm_reserved_bytes"),
            "kv_cache_bytes": step_entry.get("kv_cache_bytes"),
            "flops_per_token": step_entry["flops"] / tokens,
            "bytes_per_token": step_entry["bytes_accessed"] / tokens,
            "tokens_per_step": tokens,
            "basis": basis,
        }
        if self._mesh is not None:
            # under SPMD the compiled artifact is the PER-DEVICE
            # partitioned module, so the analyses above are per-shard
            # figures; say so, and stamp the mesh so a record reader
            # can reconstruct mesh totals (devices × per-device)
            out["mesh"] = self._mesh.describe()
            out["basis"] += ("; SPMD executable — compiler analyses "
                             "are per-device over dp×mp=%d devices"
                             % self._mesh.devices_n)
            # mp-axis activation-collective bytes (docs §5r): derived
            # from the shapes the seam recorded while the decode step
            # traced — quantized wire bytes beside the dense fp32 ring
            # equivalent, both per committed token, never faked
            out.update(self._session.collective_report())
        return out

    def cost_report(self) -> dict:
        """Cost/memory attribution of every executable this pool runs,
        read off the compiled artifacts (``jit.aot``), plus a
        ``derived`` block: the batched decode step's FLOPs and
        bytes-accessed divided over the ``slots`` tokens it commits —
        the per-token cost model the serving gauges surface
        (``serving_step_flops`` / ``serving_step_bytes_accessed`` /
        ``serving_hbm_reserved_bytes``).  ``kv_cache_bytes`` (the decode
        executable's cache-argument payload) reconciles exactly with
        ``cache_stats()['pool_bytes']`` for every layout x dtype
        (test-pinned)."""
        rep = self._session.cost_report()
        rep["pool_decode"] = self._decode_jit.cost_report()
        rep["slot_insert"] = self._insert_jit.cost_report()
        if self._chunk_jit is not None:
            # the chunk executable's attribution rides the same AOT
            # path: what one tick's bounded prompt work asks of the
            # hardware, from the artifact
            rep["prefill_chunk"] = self._chunk_jit.cost_report()
            rep["slot_admit"] = self._admit_jit.cost_report()
        rep["derived"] = self._derived_costs(self._decode_jit.last_cost())
        return rep

    def cache_stats(self) -> dict:
        """Live KV-cache accounting: layout, allocator occupancy, and
        the bytes a decode step can reach RIGHT NOW vs what a dense
        preallocation of the same pool would pin — the paged win,
        quantified from the allocator state rather than asserted."""
        # bytes a slot and layers, by the entries' kind (a model with one
        # kind of entry has one key); the expert layers' route and read
        # at the slots live now
        by_kind = {**self._by_kind_stats,
                   **self._expert_meta(len(self._active))}
        if set(self._by_kind) == {"recurrent"}:
            # O(1)-state accounting: the whole cache is [slots, d_state]
            # per layer — no positional axis, so reachable == resident
            # == the state pytree, independent of sequence length (the
            # model-class argument, quantified).  state_bytes_per_slot
            # is the capacity planner's figure: slots/GB falls out as
            # 2**30 // it.
            per_slot = self._state_bytes_slot
            state_total = per_slot * self.slots
            stats = {
                "cache_layout": self.cache_layout,
                "cache_dtype": self._layout.cache_dtype_str(self._cache),
                "decode_route": self._session.route,
                "d_state": self._layout.fingerprint_extra(self)["d_state"],
                "num_entries": len(self._cache),
                "state_bytes_per_slot": per_slot,
                "reachable_bytes": state_total,
                "pool_bytes": state_total,
                **by_kind,
            }
            if self._mesh is not None:
                stats["mesh"] = self._mesh.describe()
                # a recurrence has no attention/MLP row-parallel seams,
                # so the mode is stamped (provenance) but no collective
                # byte columns exist to report
                stats["collective_quant"] = self._session.collective_quant
            stats["per_shard"] = [
                {"shard": s, "reachable_bytes": state_total // self._dp,
                 "pool_bytes": state_total // self._dp}
                for s in range(self._dp)]
            if self._mesh is not None:
                # dp splits the slot axis; the state vector is whole
                # per slot (mp does not shard it — mesh.py axis rules)
                stats["pool_bytes_per_device"] = \
                    state_total // self._mesh.dp
            return stats
        # the positional figures run over the entries that address
        # positions; a model that mixes kinds adds its recurrent entries'
        # whole state (the same at any context) to what is resident and
        # reachable
        # (so does a window entry's ring, the same at any context: its
        # pools are held whole, slots x ring blocks and a scratch block)
        state_total = (self._state_bytes_slot
                       + self._window_bytes_slot) * self.slots
        if self._ring is not None:
            state_total += self._window_bytes_slot // self._ring[1]
        # every slot at max_len over those entries (K/V by head, scales
        # included, or a latent): what ``kv_reachable_bytes(...,
        # layout="dense")`` gives for K/V
        dense_bytes = self.slots * sum(
            b for kind, (_, b) in self._by_kind.items()
            if kind not in ("recurrent", "window"))
        # every byte figure below is dtype-aware (int8 caches count the
        # int8 K/V plus the riding fp32 scales — kv_reachable_bytes),
        # and the dtype is stamped so a serving record can never present
        # an int8 byte count as an fp32 one
        stats = {"cache_layout": self.cache_layout,
                 "cache_dtype": self._layout.cache_dtype_str(self._cache),
                 # the decode-attention route (§5l) is provenance the
                 # same way layout/dtype are: a tok/s or byte figure
                 # from the fused kernel must never be presented as a
                 # composition number
                 "decode_route": self._session.route,
                 # worst-case cache bytes one slot pins at max_len —
                 # comparable across model classes (the recurrent
                 # branch stamps the same key for its O(1) state)
                 "state_bytes_per_slot": sum(
                     b for _, b in self._by_kind.values()),
                 "dense_equiv_bytes": dense_bytes, **by_kind}
        if self._mesh is not None:
            stats["mesh"] = self._mesh.describe()
            # the mp-collective mode is provenance like layout/route: a
            # tok/s figure from quantized collectives must never be
            # presented as a dense one.  The byte columns (docs §5r)
            # appear once the decode step has traced under the seam —
            # derived from traced collective shapes, never faked
            stats["collective_quant"] = self._session.collective_quant
            stats.update(self._session.collective_report())
        if self._layout.paged:
            bs = self._block_size
            # resident = unique blocks some live slot's table row maps
            # (== the refcounted set); spilled device copies are a
            # THIRD state — not free, not resident — so the partition
            # free + mapped + spilled + scratch == num_blocks is exact
            # (test-pinned under preemption churn)
            mapped = len(self._block_refs)
            # each UNIQUE resident block counted once (a prefix-shared
            # block is readable by several slots but occupies its HBM
            # once), at its readable tokens: a block at logical index j
            # covers [j*bs, (j+1)*bs) capped at max_len — the ragged
            # final block's over-hang is masked, never attended, so it
            # must not be counted (and sharing is prefix-aligned, so a
            # shared block has the same logical index for every owner).
            # Pre-sharing this reduces exactly to the per-slot-span
            # kv_reachable_bytes formula
            seen: Dict[int, int] = {}
            for blocks in self._slot_blocks.values():
                for j, b in enumerate(blocks):
                    seen.setdefault(b, j)
            per_token = dense_bytes // (self.slots * self.max_len)
            reachable = state_total + per_token * sum(
                max(0, min((j + 1) * bs, self.max_len) - j * bs)
                for j in seen.values())
            pool_bytes = state_total + self._num_blocks * bs * per_token
            stats.update(
                block_size=bs,
                num_blocks=self._num_blocks,
                free_blocks=sum(len(fl) for fl in self._free_by_shard),
                mapped_blocks=mapped,
                spilled_blocks=len(self._spill_owner),
                reachable_bytes=reachable,
                # blocks referenced beyond their first owner — the live
                # HBM the prefix index is currently saving
                shared_blocks=self._shared_block_count(),
                pool_bytes=pool_bytes)
            # PER-SHARD accounting beside the mesh totals: the figure a
            # per-chip capacity decision (the scheduler's spill
            # thresholds, an HBM headroom alarm) must read — a
            # mesh-total-only gauge would overstate per-chip headroom
            # by dp×.  With dp == 1 this is a one-entry restatement of
            # the totals, so consumers need no mesh special-case.
            if self._dp == 1:
                # restate the totals (no rescans: cache_stats runs on
                # the per-tick gauge path)
                mapped_by = [mapped]
                spilled_by = [len(self._spill_owner)]
                reach_by = [reachable]
            else:
                # one pass per collection, bucketing by owning shard
                mapped_by = [0] * self._dp
                for b in self._block_refs:
                    mapped_by[self._shard_of_block(b)] += 1
                spilled_by = [0] * self._dp
                for b in self._spill_owner:
                    spilled_by[self._shard_of_block(b)] += 1
                reach_by = [0] * self._dp
                for b, j in seen.items():
                    reach_by[self._shard_of_block(b)] += per_token * \
                        max(0, min((j + 1) * bs, self.max_len) - j * bs)
            stats["per_shard"] = [{
                "shard": s,
                "num_blocks": self._blocks_per_shard,
                "scratch_block": self._shard_scratch(s),
                "free_blocks": len(self._free_by_shard[s]),
                "mapped_blocks": mapped_by[s],
                "spilled_blocks": spilled_by[s],
                "reachable_bytes": reach_by[s],
                "pool_bytes": pool_bytes // self._dp,
            } for s in range(self._dp)]
        else:
            dense_bytes += state_total
            stats.update(reachable_bytes=dense_bytes,
                         pool_bytes=dense_bytes)
            stats["per_shard"] = [
                {"shard": s, "reachable_bytes": dense_bytes // self._dp,
                 "pool_bytes": dense_bytes // self._dp}
                for s in range(self._dp)]
        if self._mesh is not None:
            # bytes one DEVICE holds: dp splits the slot/block axis,
            # mp splits the head axis of every K/V (and scale) leaf
            stats["pool_bytes_per_device"] = \
                stats["pool_bytes"] // self._mesh.devices_n
        return stats
