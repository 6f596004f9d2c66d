"""Continuous batching for a model that generates by diffusion over
blocks (``models.BlockDiffusionMoELM``): the ``GenerationPool`` variant
whose step commits 0..``block_length`` tokens a sequence, not one.

A sequence is cut into blocks of ``B = model.block_length`` positions
aligned to position 0.  The prompt's whole blocks are prefilled once
(bucketed, batch 1, under the block-causal mask) and spliced into the
pool's cache.  Every further block starts as the prompt's remaining
tokens, held fixed, and mask ids, and is denoised in place: of the
positions still masked, the ``n`` with the largest softmax probability
take their argmax token (static low-confidence remasking: a committed
token is never masked again).  A block of ``m`` positions to fill takes
``min(T, m)`` such steps (``T = model.denoise_steps``), step ``t``
committing ``m // steps`` tokens, one more in the first ``m % steps``.

The pool's ONE step executable runs the model over ``[slots, 2B]``
positions, two blocks a slot at the slot's cache index, against the cache
of all earlier blocks.  There is one kind of forward; a flag of the
per-slot control data says what its front half is:

- it **carries a store**: rows ``[clean block n | first state of block
  n + 1]`` at the index, which stands at block ``n``'s start, where its
  denoising left it.  All ``2B`` rows' K/V are written before the
  attention reads the pools, so block ``n + 1``'s rows see block ``n``'s
  clean K/V under the block-causal mask, the index advances by ``B``
  (block ``n`` is kept for good), and the logits, the confidence ranking
  and the commit are those of the back ``B`` rows.  A clean block's store
  costs no forward of its own: it rides the next block's first step.
- it **carries none** (a block's later denoising steps; a request's first
  block, whose predecessors the prefill wrote): rows ``[current block |
  dead rows]``.  The block's noisy K/V is written at the index, which does
  not advance, so the next step overwrites it.  The dead rows repeat the
  block's tokens a block further on.  Nothing of theirs is read: the head
  runs on the front ``B`` rows alone, and their K/V lies past the current
  block, where no live row's mask reaches and where the next carried
  store writes ``2B`` rows over it (past the table's span or the dense
  cache's end it goes to the scratch block or is dropped, as a
  speculative tail's does).  They stay finite on every route: a dead row
  sees what its position sees, its own K/V included, never nothing.

A request's last block is not stored: nothing comes after it.  Slots are
not in lockstep: in one tick some forwards carry a store and some do not.
One packed upload and one packed download a tick.  Tokens leave in
position order as the committed prefix of the block grows; budgets, EOS
and finish count tokens, not steps.  A last block commits only the
positions that were asked for; the rest stay mask ids and are never
delivered.

The chunk is ``2B`` positions whatever ``B``.  At ``B = 4`` its 8 rows
are what the fused paged kernel and the in-place K/V write take
(``ops.pallas_decode.MAX_KERNEL_QUERY_CHUNK`` is 8); at a ``B`` above 4
the gates that are there send the chunk to the composition and the
scatters (no benchmark cell runs such a ``B``).

The host runs one step behind the device (``GenerationPool.step``,
docs/DESIGN.md §5t), so a block's state, its tokens and what of it is
still masked, feeds back ON THE DEVICE: the step's packed output is the
next step's input, and after a block's last denoising step it holds the
clean tokens the carried store needs.  The upload holds only what the
host knows ahead of the download: how many tokens the step commits,
whether it carries a store, which rows are live, and the rows that START
a block (a prompt's trailing partial block, the all-masked block after a
clean one), which the step takes from the host.  With a fixed number of
denoising steps and no confidence threshold all of that is settled when a
request is admitted: ``_Cursor`` walks it on the launch side, a step
ahead of ``_Block``, which follows the downloads.

What this variant does not do is refused at construction with a typed
error (``docs/DESIGN.md``): prefix sharing, chunked prefill, preemption
and spill, a prefill-only tier, a mesh, an int8 cache, sampling with a
temperature, LoRA adapters.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError, PreconditionNotMetError
from ..jit import aot
from .generation import GenerationPool, _SlotState

__all__ = ["BlockDiffusionPool", "commit_plan"]


def commit_plan(to_fill: int, denoise_steps: int) -> List[int]:
    """Tokens each denoising step of one block commits: ``to_fill``
    positions over ``min(denoise_steps, to_fill)`` steps, as evenly as
    whole numbers allow, the larger counts first."""
    steps = min(int(denoise_steps), int(to_fill))
    if steps < 1:
        return []
    base, extra = divmod(int(to_fill), steps)
    return [base + (1 if t < extra else 0) for t in range(steps)]


class _Block:
    """Host mirror of one slot's current block, in plain lists (a block
    is a handful of positions: numpy would cost more than it saves).
    ``toks``/``masked`` are what the next step is given; ``limit`` ends
    the positions this request may fill (block-local); ``delivered`` is
    how far tokens have left; ``plan`` holds the commit counts of the
    denoising steps still to run; ``steps[i]`` is the step that committed
    position ``i``."""

    __slots__ = ("toks", "masked", "limit", "delivered", "plan", "step",
                 "steps")

    def __init__(self, toks, masked, limit, delivered, plan):
        self.toks, self.masked = toks, masked
        self.limit, self.delivered, self.plan = limit, delivered, plan
        self.step = 0                       # denoising steps run so far
        self.steps = [0] * len(toks)


class _Cursor:
    """Where the LAUNCHES of one slot stand, a step ahead of the slot's
    ``_Block``: ``plan`` holds the commit counts of the current block's
    denoising steps still to launch; ``left`` is what the request may
    fill after this block; ``row`` is the block's first state, which
    the next launch hands to the device (None once it has: the state
    then feeds back there); ``pos`` is where the block starts.  Once
    ``plan`` is empty the block is clean, and the next launch, if the
    request may fill more, is the NEXT block's first step with the store
    of this one on it."""

    __slots__ = ("plan", "left", "row", "pos")

    def __init__(self, plan, left, row, pos):
        self.plan, self.left, self.row, self.pos = plan, left, row, pos


_REFUSED = {
    "prefill_chunk_tokens": "chunked prefill (a chunk would have to end on "
                            "a block boundary and carry the block mask)",
    "prefix_sharing": "prefix sharing (it rides on chunked prefill)",
    "mesh": "a mesh (the expert layer has no ep axis yet)",
    "prefill_only": "a prefill-only tier (a half-denoised block has no "
                    "K/V hand-off)",
    "spill_dir": "a spill tier (a half-denoised block is not spilled)",
    "collective_quant": "quantized collectives (there is no mesh)",
    "collective_quant_scale": "quantized collectives (there is no mesh)",
}


class BlockDiffusionPool(GenerationPool):
    """See the module docstring.  Takes ``GenerationPool``'s keyword set
    as it is (``slots``, ``buckets``, ``cache_layout``, ``block_size``,
    ``num_blocks``, ``eos_id``, ``cache_dtype``, ``route``, ...) and
    refuses by name what it cannot honour."""

    def __init__(self, model, max_len: int, **pool_kwargs):
        if getattr(model, "generation", None) != "block_diffusion":
            raise InvalidArgumentError(
                "BlockDiffusionPool serves a model that declares "
                "generation='block_diffusion' (models.BlockDiffusionMoELM)"
                "; got %s" % type(model).__name__)
        for key, what in _REFUSED.items():
            if pool_kwargs.get(key) not in (None, False):
                raise InvalidArgumentError(
                    "%s=%r: generation by diffusion over blocks does not "
                    "support %s" % (key, pool_kwargs[key], what))
        if pool_kwargs.get("spill_tier", "host") != "host":
            raise InvalidArgumentError(
                "spill_tier=%r: generation by diffusion over blocks does "
                "not support %s" % (pool_kwargs["spill_tier"],
                                    _REFUSED["spill_dir"]))
        if pool_kwargs.get("temperature", 0.0):
            raise InvalidArgumentError(
                "generation by diffusion over blocks commits argmax "
                "tokens: temperature must be 0, got %r"
                % (pool_kwargs["temperature"],))
        pool_kwargs.setdefault("cache_dtype", "bfloat16")
        if jnp.dtype(pool_kwargs["cache_dtype"]) == jnp.int8:
            raise InvalidArgumentError(
                "cache_dtype='int8': generation by diffusion over blocks "
                "keeps a float K/V cache (no grouped-head int8 kernel)")
        self._B = int(model.block_length)
        # the step's chunk: two blocks a slot (module docstring)
        self._rows_a_slot = 2 * self._B
        self._T = int(model.denoise_steps)
        self._mask_id = int(model.mask_token_id)
        if int(max_len) % self._B:
            raise InvalidArgumentError(
                "max_len=%d is no multiple of block_length=%d: a block "
                "may not straddle the cache's end" % (max_len, self._B))
        if pool_kwargs.get("cache_layout") == "paged" \
                and int(pool_kwargs.get("block_size", 32)) % self._B:
            raise InvalidArgumentError(
                "block_size=%d is no multiple of block_length=%d: a "
                "block of positions may not straddle two cache blocks"
                % (pool_kwargs.get("block_size", 32), self._B))
        super().__init__(model, max_len, **pool_kwargs)
        donate = pool_kwargs.get("donate")
        if donate is None:
            donate = jax.default_backend() != "cpu"
        # the pool's two executables of its own, in place of the batched
        # one-token step and the session's sampling prefill (which are
        # never compiled here); ``slot_insert`` is the base pool's
        self._decode_jit = aot.AotFunction(
            jax.jit(self._block_step,
                    donate_argnums=(2,) if donate else ()),
            key_fn=lambda *a: "block_step", name="block_step",
            meta_fn=lambda p, b, cache, *r: {
                "kv_cache_bytes": aot.kv_arg_bytes(cache)})
        self._prefill_jit = aot.AotFunction(
            jax.jit(self._block_prefill),
            key_fn=lambda p, b, ids, *r: aot.shape_key(ids),
            name="block_prefill")
        self._blocks: Dict[int, _Block] = {}
        self._cursors: Dict[int, _Cursor] = {}
        # the blocks' state on the device, ``[slots, 2B]`` as the step
        # returns it (None until the first launch)
        self._carry = None
        # a row of the packed upload that hands the device no state: a
        # free slot's, and the front of a live one whose state feeds
        # back on the device
        self._no_row = [0] * (2 * self._B)
        # what the step did, ever: read by the engine's counters
        self.forwards_denoise = 0
        self.stores_carried = 0
        self.tokens_committed = 0

    # -- traced bodies ---------------------------------------------------
    def _block_prefill(self, param_vals, buf_vals, ids, whole):
        """The batch-1 cache of a bucket-padded prompt with the index at
        ``whole``, the length of the prompt's whole blocks.  The block
        mask keeps every row of a whole block from seeing anything past
        its block, so the rows after ``whole`` (a trailing partial block,
        the padding) write K/V that nothing attends and the first step
        overwrites.  No token is taken here, and no logits: the head is
        dead code in this program."""
        cache = self._model.gen_decode_cache(
            1, self.max_len, self._cache_dtype, layout=self._kv_layout,
            block_size=self._block_size)
        _, cache = self._session._run_model(param_vals, buf_vals, ids,
                                            cache)
        return self._layout.finalize_prefill(
            cache, jnp.asarray(whole, jnp.int32), self.max_len)

    def _block_step(self, param_vals, buf_vals, cache, carry, ctl):
        """One forward over two blocks a slot, ``[slots, 2B]`` rows at
        the cache index.  ``carry`` is the last step's packed output,
        int32 ``[slots, 2B]``: every block's tokens and which of them
        are still to fill.  ``ctl`` is the tick's packed upload, int32
        ``[slots, 2B + 4]``: a block's first state in ``carry``'s form,
        how many tokens to commit, whether the forward carries the store
        of the block in ``carry``, whether the slot is live, and whether
        the slot takes its state from ``ctl`` (it starts a block) and not
        from ``carry``.  A forward that carries a store runs ``carry``'s
        clean tokens in front of the new block, denoises the back rows
        and moves the index past the stored block; one that carries none
        denoises the front rows and repeats them behind as dead rows
        (module docstring).  Returns the cache and the packed output
        ``[slots, 2B]``, the denoised block's tokens after the commit and
        what is still masked: the download, and the next step's
        ``carry``."""
        bl = self._B
        state = jnp.where(ctl[:, 2 * bl + 3:] != 0, ctl[:, :2 * bl], carry)
        toks, masked = state[:, :bl], state[:, bl:] != 0
        count = ctl[:, 2 * bl]
        active = ctl[:, 2 * bl + 2] != 0
        store = (ctl[:, 2 * bl + 1] != 0) & active
        ids = jnp.concatenate(
            [jnp.where(store[:, None], carry[:, :bl], toks), toks], axis=1)
        # how far behind the index the denoised block stands: a stored
        # block's length, which is also what the index gains
        behind = jnp.where(store, bl, 0)
        given = cache
        if self._layout.paged:
            cache = self._masked_tables(cache, active)
        # the head on the denoised block's rows alone
        logits, new_cache = self._session._run_model(
            param_vals, buf_vals, ids, cache, last=behind)
        with jax.named_scope("sample"):
            lf = logits.astype(jnp.float32)
            best = jnp.argmax(lf, axis=-1).astype(jnp.int32)
            # log of the largest softmax probability: the confidence
            conf = jnp.max(lf, axis=-1) - jax.nn.logsumexp(lf, axis=-1)
            score = jnp.where(masked, conf, -jnp.inf)
            at = jnp.arange(bl)
            # rank among the block's positions, the earlier first in a tie
            ahead = (score[:, None, :] > score[:, :, None]) | (
                (score[:, None, :] == score[:, :, None])
                & (at[None, None, :] < at[None, :, None]))
            rank = jnp.sum(ahead, axis=-1)
            commit = masked & (rank < count[:, None])
            out = jnp.concatenate(
                [jnp.where(commit, best, toks),
                 (masked & ~commit).astype(jnp.int32)], axis=1)
        with jax.named_scope("cache_freeze"):
            # the forward moved every index by 2B: a carried store keeps
            # B of it, the block now stored, and nothing else moves
            new_cache = [c._replace(index=g.index + behind)
                         for c, g in zip(new_cache, given)]
        if cache is not given:
            new_cache = [c._replace(table=g.table)
                         for c, g in zip(new_cache, given)]
        return new_cache, out

    # -- host API --------------------------------------------------------
    def submit(self, input_ids, max_new_tokens: int, request_id=None,
               **kwargs):
        if kwargs.get("temperature") or (
                kwargs.get("_sampling") is not None
                and kwargs["_sampling"].temperature):
            raise InvalidArgumentError(
                "generation by diffusion over blocks commits argmax "
                "tokens: a request's temperature must be 0")
        if kwargs.get("adapter"):
            raise InvalidArgumentError(
                "generation by diffusion over blocks serves the base "
                "model only (adapter must be 0)")
        return super().submit(input_ids, max_new_tokens, request_id,
                              **kwargs)

    def can_preempt(self, request_id) -> bool:
        return False

    def preempt(self, request_id) -> dict:
        raise PreconditionNotMetError(
            "generation by diffusion over blocks does not preempt: a "
            "half-denoised block is not spilled")

    def _new_block(self, fixed, remaining: int) -> _Block:
        """The block that starts with the tokens ``fixed`` (a prompt's
        trailing partial block, else none) and may fill ``remaining``
        more."""
        bl, r = self._B, len(fixed)
        fill = min(bl - r, remaining)
        toks = [int(t) for t in fixed] + [self._mask_id] * (bl - r)
        masked = [0] * r + [1] * fill + [0] * (bl - r - fill)
        return _Block(toks, masked, r + fill, r,
                      commit_plan(fill, self._T))

    def _prefill_row(self, req):
        """The prompt's whole blocks, prefilled under the block mask; no
        token is taken (``tok`` is None)."""
        n = len(req.ids)
        padded = np.zeros((1, self._session._bucket_for(n)), np.int32)
        padded[0, :n] = req.ids
        whole = n // self._B * self._B
        params, bufs = self._weights()
        return self._prefill_jit(params, bufs, jnp.asarray(padded),
                                 whole), whole, None

    def _start_slot(self, slot: int, req, tok) -> None:
        """No first token: the slot goes live with the block that starts
        with the prompt's trailing partial block."""
        self._active[slot] = _SlotState(
            req.rid, req.ids, [], req.max_new_tokens,
            priority=req.priority, tenant=req.tenant,
            deadline=req.deadline, seq=req.seq, sampling=req.sampling,
            adapter=req.adapter)
        whole = len(req.ids) // self._B * self._B
        blk = self._blocks[slot] = self._new_block(
            req.ids[whole:], req.max_new_tokens)
        self._cursors[slot] = _Cursor(
            list(blk.plan), req.max_new_tokens - sum(blk.plan),
            blk.toks + blk.masked, whole)

    def _launchable(self, slot: int, state) -> bool:
        # until the last denoising step of the request's last block has
        # been launched (that block is never stored)
        cur = self._cursors[slot]
        return bool(cur.plan) or cur.left > 0

    def _control(self) -> np.ndarray:
        """The tick's packed upload (see ``_block_step``), every cursor
        of the step's rows moved past it."""
        rows = [self._no_row + [0, 0, 0, 0]] * self.slots
        for slot, st in self._rows:
            cur = self._cursors[slot]
            store = 0
            if not cur.plan:
                # the block is clean: the next starts all masked a block
                # further on, and its first step carries this one's store
                blk = self._new_block((), cur.left)
                cur.plan, cur.left = list(blk.plan), cur.left - sum(blk.plan)
                cur.row, cur.pos = blk.toks + blk.masked, cur.pos + self._B
                store = 1
            front = self._no_row if cur.row is None else cur.row
            take = 0 if cur.row is None else 1
            cur.row = None
            rows[slot] = front + [cur.plan.pop(0), store, 1, take]
        return np.array(rows, np.int32)

    # -- the tick's hooks: ONE forward over every live slot's block ------
    def _sync_step_inputs(self):
        if self._carry is None:
            self._carry = jnp.zeros((self.slots, 2 * self._B), jnp.int32)
        return self._weights() + (self._control(),)

    def _decode_meta(self, params, bufs, ctl) -> dict:
        # a denoising step commits exactly what its plan asks: known
        # before the dispatch, so the span carries it.  ``store`` counts
        # the slot-forwards that do nothing but store: there is none
        bl, live = self._B, len(self._rows)
        committed = int(ctl[:, 2 * bl].sum())
        meta = dict(live=live, slots=self.slots, rows=live * 2 * bl,
                    store=0, stores_carried=int(ctl[:, 2 * bl + 1].sum()),
                    committed=committed,
                    tokens_per_forward=committed / live)
        if self._layout.paged:
            # the chunk's last row sees to the end of its block, two
            # blocks from the index; the cursor of a row that carries a
            # store stands a block further already
            bs = self._block_size
            meta["live_blocks"] = sum(
                min((self._cursors[slot].pos - 1
                     + bl * (2 - int(ctl[slot, 2 * bl + 1]))) // bs + 1,
                    self._max_blocks)
                for slot, _ in self._rows)
            meta["table_blocks"] = self.slots * self._max_blocks
            meta.update(self._entries_meta)
        meta.update(self._expert_meta(live))
        return meta

    def _launch(self, params, bufs, ctl):
        self._cache, self._carry = self._decode_jit(
            params, bufs, self._cache, self._carry, ctl)
        return self._carry

    def _deliver(self, out: np.ndarray) -> None:
        """Take the step's download into the block of every row of it
        that is still live: start the next block where the last was
        clean (the step carried its store), note the commits, and let
        the tokens of the grown committed prefix go in position order
        (``_commit`` finishes on EOS or budget)."""
        bl = self._B
        rows = out.tolist()
        for slot, st in self._rows:
            blk = self._blocks[slot]
            if not blk.plan:
                # the block was clean: this step kept its K/V, the index
                # stands at the next block, which started all masked
                self.stores_carried += 1
                blk = self._blocks[slot] = self._new_block((), st.remaining)
            self.tokens_committed += blk.plan.pop(0)
            blk.step += 1
            toks, masked = rows[slot][:bl], rows[slot][bl:]
            for i in range(bl):
                if blk.masked[i] and not masked[i]:
                    blk.steps[i] = blk.step
            blk.toks, blk.masked = toks, masked
            self._commit(slot, self._leaving(blk))
            self.token_commit_step = None
        self.forwards_denoise += len(self._rows)

    def _leaving(self, blk: _Block):
        """The tokens of ``blk``'s committed prefix that have not left
        yet, in position order, ``token_commit_step`` set to the step
        that committed each before it is handed on."""
        while blk.delivered < blk.limit and not blk.masked[blk.delivered]:
            self.token_commit_step = blk.steps[blk.delivered]
            blk.delivered += 1
            yield blk.toks[blk.delivered - 1]

    def _finish(self, slot: int):
        self._blocks.pop(slot, None)
        self._cursors.pop(slot, None)
        super()._finish(slot)

    def release(self, slot: int):
        rid = super().release(slot)
        self._blocks.pop(slot, None)
        self._cursors.pop(slot, None)
        return rid

    def reset(self):
        super().reset()
        self._blocks.clear()
        self._cursors.clear()
        self._carry = None

    def compile_counts(self) -> dict:
        return {"block_prefill": int(self._prefill_jit._cache_size()),
                "block_step": int(self._decode_jit._cache_size()),
                "slot_insert": int(self._insert_jit._cache_size())}

    def cost_version(self) -> int:
        return (self._prefill_jit.compiles + self._decode_jit.compiles
                + self._insert_jit.compiles)

    def block_stats(self) -> dict:
        """Slot-forwards, those of them that carried a store, and tokens
        committed, ever."""
        return {"forwards_denoise": self.forwards_denoise,
                "stores_carried": self.stores_carried,
                "tokens_committed": self.tokens_committed}
