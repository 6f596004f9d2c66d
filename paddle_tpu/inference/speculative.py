"""Slot-batched speculative decoding: the draft/verify pool variant.

``SpeculativePool`` is ``GenerationPool`` with the decode step swapped
for a speculative ROUND (jit/speculative.py has the single-request
anatomy): a small draft model runs ``spec_k`` batched greedy decode
steps over its own slot cache, then the target judges every slot's
``[pending, d_1..d_K]`` chunk in ONE per-slot chunk forward — the
multi-token append of ``_decode_forward``/``_paged_decode_forward``
with a ``[slots]`` index vector, so EVERY slot accepts a different
prefix length in the same fixed-shape dispatch.  Rejection rewinds by
moving each row's index pointer; the rejected drafts' K/V become stale
rows the next chunk overwrites (paged writes past a slot's reservation
land in the scratch block through the padded table, exactly the
slot-churn masking of docs/DESIGN.md §5b — scales included, §5d).

Per ``step()``, each active slot emits between 1 and ``spec_k + 1``
tokens (all of them EXACTLY what target-only greedy decode would have
emitted); EOS inside an accepted chunk truncates the commit AT the EOS
(``GenerationPool._commit``) — the accepted tail behind it is never
emitted, matching the one-token-at-a-time loop's stopping point.

Fixed compile budget on top of the base pool's: one draft prefill per
bucket + ONE draft decode step (the round's K dispatches and the
catch-up all reuse it) + one draft fixup + one draft slot-insert for
the draft side; one target prefill per bucket + ONE verify step for the
target — no compile ever depends on an acceptance length.

The scheduler above (``serving.ServingEngine``) drives this pool
through the unchanged ``submit``/``step``/``cancel``/``release``
surface — lifecycle, deadlines and cancellation apply to speculative
slots verbatim; the engine only gains an ``acceptance_rate`` gauge.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError
from ..jit import aot
from ..jit.decode import DecodeSession
from ..jit.speculative import (acceptance_summary, check_draft_compatible,
                               greedy_accept)
from .generation import GenerationPool

__all__ = ["SpeculativePool"]


def _refuse_recurrent(layout: str, layers: str):
    raise InvalidArgumentError(
        "speculative decoding does not support cache_layout=%r (%s): "
        "verify-rewind moves a POSITIONAL index pointer back over "
        "rejected drafts, but a recurrent carry folds every step into "
        "one state vector — there is no earlier position to rewind to "
        "without re-running the prefix; use GenerationPool for "
        "recurrent/SSM models" % (layout, layers))


class SpeculativePool(GenerationPool):
    """Continuous batching whose step is a draft/verify round.

    ``model`` is the target; ``draft_model`` a (typically much smaller)
    causal model sharing the target's token id space (a typed error at
    construction names both vocab sizes otherwise).  Greedy only — the
    acceptance rule that preserves a SAMPLED target distribution is
    rejection sampling, which is future work; greedy acceptance is
    exact by construction, so the pool's output is token-identical to a
    plain ``GenerationPool`` over the same target.

    The target cache takes the usual ``cache_layout``/``cache_dtype``
    knobs; the draft keeps a dense fp32 slot cache (it is small by
    design — the paged/int8 machinery earns its complexity on the
    target's HBM bill, not the draft's).
    """

    def __init__(self, model, draft_model, max_len: int, spec_k: int = 4,
                 **pool_kwargs):
        temperature = pool_kwargs.pop("temperature", 0.0)
        if float(temperature) != 0.0:
            raise InvalidArgumentError(
                "speculative decoding is greedy-only (temperature=0): "
                "got temperature=%r; use GenerationPool for sampled "
                "generation" % (temperature,))
        if int(spec_k) < 1:
            raise InvalidArgumentError(
                "spec_k must be >= 1 draft tokens per round, got %r"
                % (spec_k,))
        if pool_kwargs.get("cache_layout") == "recurrent":
            _refuse_recurrent("recurrent",
                              "every layer keeps a state of constant size")
        if pool_kwargs.get("prefill_only"):
            raise InvalidArgumentError(
                "prefill_only=True: the speculative pool's draft state "
                "does not cross the K/V hand-off — the prefill tier "
                "runs a plain GenerationPool")
        check_draft_compatible(draft_model, model)
        # every other keyword is the base pool's, taken as it is (a
        # DROP-IN for GenerationPool under ServingEngine's
        # **pool_kwargs): top_k/top_p are ignored at temperature=0
        # exactly as the plain pool ignores them; chunked prefill +
        # prefix sharing apply to the TARGET cache verbatim, while the
        # draft twin keeps its bucketed dense prefill — the draft is
        # small by design, and its prompt forward runs once at
        # activation, not per tick
        super().__init__(model, max_len, **pool_kwargs)
        if self._layout.windowed:
            raise InvalidArgumentError(
                "speculative decoding does not support cache_layout=%r: a "
                "verify chunk of several positions that starts mid-way "
                "may ask a window entry for keys its ring has already "
                "overwritten, and a rewind cannot bring them back (the "
                "windowed kernel takes one query a row)" % (self.cache_layout,))
        if not self._layout.positional:
            # a model that mixes kinds: asked for by its K/V layout, so
            # only its cache entries say that some layers cannot rewind
            _refuse_recurrent(self.cache_layout,
                              self._layout.recurrent_entries())
        buckets, donate, mesh, route = (
            pool_kwargs.get("buckets"), pool_kwargs.get("donate"),
            pool_kwargs.get("mesh"), pool_kwargs.get("route", "auto"))
        # the mode is accepted (drop-in under ServingEngine's
        # **pool_kwargs) and validated by the target session, but the
        # speculative VERIFY step keeps dense collectives this PR: its
        # multi-token rows amortize the mp all-reduce over spec_k+1
        # tokens, so the single-token decode step is where the
        # bandwidth win lives (ROADMAP names the verify leg as the
        # on-TPU follow-up)
        self.spec_k = int(spec_k)
        # the draft session owns the draft binding and its bucketed
        # batch-1 prefill (compiled once per bucket); its decode step is
        # unused — the pool's slot-batched draft step below replaces it.
        # Under a mesh the draft shares it: draft weights place by the
        # same mp axis rules, the draft slot cache shards over dp like
        # the target's
        # the draft shares the route: its batched decode step is a
        # decode-family executable like the target's (Lq=1, so the
        # fused kernel applies to it the same way)
        self._draft_session = DecodeSession(
            draft_model, max_len, buckets=buckets, temperature=0.0,
            donate=donate, mesh=mesh, route=route)
        self._draft_cache = self._new_draft_cache()
        if donate is None:
            donate = jax.default_backend() != "cpu"
        dn = (2,) if donate else ()
        self._draft_decode_jit = jax.jit(self._draft_decode,
                                         donate_argnums=dn)
        self._draft_fixup_jit = jax.jit(self._draft_fixup,
                                        donate_argnums=dn)
        self._draft_insert_jit = jax.jit(
            self._draft_insert, donate_argnums=(0,) if donate else ())
        self._verify_jit = jax.jit(self._pool_verify, donate_argnums=dn)
        # AOT routing (jit.aot): same contract as the base pool — every
        # shape is pool-fixed, so each wrapper holds exactly the
        # executables the compile-count tests pin, and the verify step
        # (the target's whole per-round dispatch) carries the target
        # cache's kv_cache_bytes for the reconciliation contract
        self._draft_decode_jit = aot.AotFunction(
            self._draft_decode_jit,
            key_fn=lambda p, b, cache, toks, *r: aot.shape_key(toks),
            name="draft_decode")
        self._draft_fixup_jit = aot.AotFunction(
            self._draft_fixup_jit,
            key_fn=lambda p, b, cache, toks, *r: aot.shape_key(toks),
            name="draft_fixup")
        self._draft_insert_jit = aot.AotFunction(
            self._draft_insert_jit,
            key_fn=lambda *a: "draft_insert", name="draft_insert")
        self._verify_jit = aot.AotFunction(
            self._verify_jit,
            key_fn=lambda p, b, cache, chunk, *r: aot.shape_key(chunk),
            name="verify",
            meta_fn=lambda p, b, cache, *r: {
                "kv_cache_bytes": aot.kv_arg_bytes(cache)})
        self._draft_state_cache = None
        # the RUNTIME spec-K: the serving engine's degradation ladder
        # steps it down under SLO burn (fewer draft steps per round =
        # less wasted draft work when acceptance pays badly under
        # pressure) and restores it when the alert clears.  spec_k
        # stays the compiled CEILING; the first round at a NEW k_active
        # compiles one verify executable for its [slots, k+1] chunk
        # (cached — stepping back and forth is free thereafter), and
        # the fixup executable takes k as a traced scalar so its one
        # compilation serves every setting
        self._spec_k_active = self.spec_k
        self._drafted = 0
        self._accepted = 0
        self._rounds = 0

    # -- traced bodies ---------------------------------------------------
    def _draft_decode(self, param_vals, buf_vals, cache, toks, active):
        """One batched greedy draft step; inactive slots frozen (their
        index does not advance) like the base pool's decode step."""
        sess = self._draft_session
        logits, new_cache = sess._run_model(param_vals, buf_vals,
                                            toks[:, None], cache)
        tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        new_cache = [c._replace(index=jnp.where(active, c.index,
                                                old.index))
                     for c, old in zip(new_cache, cache)]
        return new_cache, jnp.where(active, tok, 0)

    def _draft_fixup(self, param_vals, buf_vals, cache, toks, accepted,
                     active, k_eff):
        """Post-verify draft maintenance, one dispatch: the catch-up
        write (fully-accepted rows never wrote d_K's K/V — ``toks`` is
        the d_K vector) plus the rejection REWIND (every active row's
        index moves to its accepted prefix: active rows advanced exactly
        ``k_eff`` during drafting, so the rewound index is
        ``idx - k_eff + accepted + 1`` — for catch-up rows that equals
        the position just written).  ``k_eff`` is a TRACED scalar, not a
        closure constant: the runtime spec-K (``set_spec_k``) changes
        the round's draft count without retracing, and a baked-in
        ``self.spec_k`` would silently rewind by the wrong amount the
        moment the executable (keyed on ``toks``'s shape alone) was
        reused at a different setting.  Rows with a partial acceptance
        also write ``toks`` at their stale position; harmless, because
        the next round's chunk overwrites every stale row before the
        index could ever reach it."""
        sess = self._draft_session
        idx_pre = cache[0].index
        _logits, new_cache = sess._run_model(param_vals, buf_vals,
                                             toks[:, None], cache)
        new_idx = jnp.where(active,
                            idx_pre - k_eff + accepted + 1,
                            idx_pre)
        return [c._replace(index=new_idx) for c in new_cache]

    def _draft_insert(self, pool_cache, row_cache, slot, length):
        """Splice a batch-1 draft prefill into ``slot`` (dense fp32 —
        the draft-side half of the base pool's ``_insert``; the layout's
        own splice, so whatever payload the draft's entries keep, K/V or
        a latent, goes in whole)."""
        return self._draft_session._layout.insert_row(
            pool_cache, row_cache, slot, length)

    def _pool_verify(self, param_vals, buf_vals, cache, chunk, active,
                     adapter):
        """One per-slot chunk forward of the target over every slot's
        ``[pending, d_1..d_K]``; acceptance, emission and the index
        rewind all happen IN-TRACE, so the acceptance length is data
        and the step compiles exactly once.  ``adapter`` is the pool's
        per-slot LoRA id vector (docs §5q): the target judges every
        row under ITS adapter inside the one executable — the draft
        proposes from the base model, which only costs acceptance rate,
        never correctness (emission is always the target's own argmax).
        Inactive slots are frozen: paged table rows masked to scratch
        before the write (slot-churn discipline), emitted tokens
        zeroed, index unchanged."""
        sess = self._session
        idx0 = cache[0].index                                # [slots]
        given = cache
        if self._layout.paged:
            # inactive rows' tables are scratch-routed FOR the step
            # (each slot to ITS shard's scratch block) and their index
            # reads 0, but both are restored in the returned cache
            # (the index from ``idx0``): under chunked prefill an
            # inactive slot can be mid-prompt, and persisting the
            # masked row would wipe its mapping
            cache = self._masked_tables(cache, active)
        logits, new_cache = sess._run_model(param_vals, buf_vals, chunk,
                                            cache, adapter)
        m, emitted = greedy_accept(logits, chunk, active)    # [S], [S,K+1]
        new_idx = jnp.where(active, idx0 + m + 1, idx0)
        new_cache = [c._replace(index=new_idx) for c in new_cache]
        if cache is not given:
            new_cache = [c._replace(table=g.table)
                         for c, g in zip(new_cache, given)]
        # pending = each row's LAST emitted token, the next round's
        # draft input — computed here so the steady state feeds straight
        # back on-device
        pending = jnp.take_along_axis(emitted, m[:, None], axis=1)[:, 0]
        return new_cache, emitted, m, pending

    # -- host API --------------------------------------------------------
    def _on_activated(self, slot, rid, ids):
        """The draft-side twin of slot activation: the newly activated
        slot gets a draft prefill of the same prompt spliced into the
        draft slot cache (the draft's own sampled first token is
        discarded — the target's is the ground truth the draft
        continues from).  Fires for BOTH prefill modes — the bucketed
        one-shot path and the chunked path's final chunk — because the
        base pool funnels every activation through ``_activate``."""
        row_cache, _tok, _ = self._draft_session.prefill(
            ids[None], self._draft_session.sampling_state(1, seed=0))
        self._draft_cache = self._draft_insert_jit(
            self._draft_cache, row_cache,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(len(ids), jnp.int32))

    def submit(self, input_ids, max_new_tokens: int, request_id=None,
               priority: int = 0, tenant=None, deadline=None,
               temperature=None, top_k=None, top_p=None, seed=None,
               adapter: int = 0, _sampling=None):
        req_t = _sampling.temperature if _sampling is not None \
            else temperature
        if req_t is not None and float(req_t) != 0.0:
            # greedy acceptance emits the target's argmax; honouring a
            # sampled request here would need the rejection-sampling
            # acceptance rule to preserve the target distribution
            raise InvalidArgumentError(
                "speculative decoding is greedy-only (temperature=0); "
                "got per-request temperature=%r — submit sampled "
                "requests to a plain GenerationPool/ServingEngine"
                % (req_t,))
        ids = np.asarray(getattr(input_ids, "value", input_ids))
        if self._chunk_tokens is not None and ids.ndim == 1 and ids.size:
            # the TARGET needs no bucket under chunked prefill, but the
            # draft twin still prefills through its buckets at
            # activation — fail at submit, not mid-tick
            self._draft_session._bucket_for(ids.shape[0])
        return super().submit(input_ids, max_new_tokens,
                              request_id=request_id, priority=priority,
                              tenant=tenant, deadline=deadline,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, seed=seed, adapter=adapter,
                              _sampling=_sampling)

    def set_spec_k(self, k: int) -> None:
        """Change the RUNTIME draft count per round, within the
        compiled ceiling ``[1, spec_k]`` — the degradation ladder's
        reduce-spec-K rung.  Takes effect next round; greedy output is
        token-identical at every setting (acceptance always emits the
        target's own argmax tokens).  The first round at a new ``k``
        compiles one verify executable for the narrower chunk, cached
        thereafter; the draft/fixup executables are shared across every
        setting (``k`` is traced data in the fixup)."""
        k = int(k)
        if not 1 <= k <= self.spec_k:
            raise InvalidArgumentError(
                "spec_k override must be in [1, %d] (the constructed "
                "spec_k is the compiled ceiling — headroom was reserved "
                "for it at construction), got %r" % (self.spec_k, k))
        self._spec_k_active = k

    @property
    def spec_k_active(self) -> int:
        """The runtime draft count per round (<= the ``spec_k``
        ceiling; stepped down/up by the degradation ladder)."""
        return self._spec_k_active

    def _preempt_guard(self, slot, st) -> None:
        """Preempting a speculative slot requires the draft twin to be
        re-prefillable at resume: the draft's bucketed prefill must
        cover prompt+committed-1 positions — the same bucket-coverage
        constraint deep recovery already imposes (docs/DESIGN.md §5f).
        Checked at PREEMPT time so the failure is a typed error at the
        decision point, never a mid-refill surprise at resume."""
        self._draft_session._bucket_for(
            len(st.ids) + max(0, len(st.tokens) - 1))

    def _adopt_guard(self, ids, tokens) -> None:
        """Adopting a crashed engine's disk-spilled state (docs §5m)
        ends in a resume, which re-prefills the draft twin — the same
        bucket-coverage constraint as ``_preempt_guard``, checked at
        the adoption decision so an uncoverable request falls back to
        the prompt+committed resubmit path instead of dying mid-refill."""
        self._draft_session._bucket_for(
            len(ids) + max(0, len(tokens) - 1))

    def config_fingerprint(self) -> dict:
        """The base fingerprint plus the draft geometry: a journal
        written by a speculative engine replays byte-identically on a
        plain engine too (greedy acceptance emits the target's own
        argmax), but the fingerprint is an equality contract — adopting
        across pool variants is a config change the operator must make
        deliberately, not a silent fallback."""
        fp = super().config_fingerprint()
        fp["spec_k"] = self.spec_k
        return fp

    def _on_resumed(self, slot, sp) -> None:
        """Restore the draft twin for a resumed slot: re-prefill it
        over prompt + committed[:-1] — exactly the positions the target
        cache was restored to (index = prompt+committed-1; the LAST
        committed token is the next round's first chunk element, its
        K/V unwritten on both sides).  The draft K/V only shape
        PROPOSALS — greedy acceptance emits the target's own argmax
        either way — so this is an acceptance-rate restoration, with
        byte-identity guaranteed by the target side alone."""
        ids = sp.ids if len(sp.tokens) <= 1 else np.concatenate(
            [sp.ids, np.asarray(sp.tokens[:-1], np.int32)])
        row_cache, _tok, _ = self._draft_session.prefill(
            ids[None], self._draft_session.sampling_state(1, seed=0))
        self._draft_cache = self._draft_insert_jit(
            self._draft_cache, row_cache,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(len(ids), jnp.int32))

    # -- the tick's hooks: a ROUND for the step --------------------------
    # Each active slot commits 1 to ``spec_k + 1`` tokens a tick.  The
    # engine's recovery treats a failed round exactly like a failed
    # decode step (rebuild + resubmit, token-identical greedy); under
    # chunked prefill the target-side prompt work runs before the round
    # as in the base pool (draft prefill still happens at activation,
    # via _on_activated).
    # a round's draft rewind reads the verify it follows, and a row that
    # commits less than its round ends: nothing of that is shown correct
    # one round ahead, so the host stays level with the device here
    _depth = 0

    def _sync_step_inputs(self):
        if self._draft_state_cache is None:
            self._draft_state_cache = self._draft_session._state_vals()
        return super()._sync_step_inputs() + self._draft_state_cache

    def _decode_meta(self, *inputs) -> dict:
        return dict(spec_k=self.spec_k, live=len(self._rows),
                    slots=self.slots)

    def _launch(self, params, bufs, dparams, dbufs):
        """The round's device work: K draft steps, one verify, one
        draft fixup (K = the runtime ``spec_k_active``).  Returns
        ``(emitted_dev, m_dev)``, which the tick downloads together:
        both transfers start before the host blocks, where two fetches
        in turn would pay two round trips a round over a thin
        transport."""
        k = self._spec_k_active
        d_toks = []
        tok = self._tok_dev
        for _ in range(k):
            self._draft_cache, tok = self._draft_decode_jit(
                dparams, dbufs, self._draft_cache, tok,
                self._active_dev)
            d_toks.append(tok)
        chunk = jnp.concatenate(
            [self._tok_dev[:, None]] + [x[:, None] for x in d_toks],
            axis=1)
        # the pending vector (each row's last emitted token) is next
        # round's draft input, fed straight back on-device: it stands
        # while every slot commits its full round, and a slot that
        # commits less finishes; the row that takes its place joins the
        # vector on the device (``_patch_carry``)
        self._cache, emitted_dev, m_dev, self._tok_dev = self._verify_jit(
            params, bufs, self._cache, chunk, self._active_dev,
            self._adapter_dev)
        # catch-up + rewind for the draft cache (one dispatch; d_K is
        # the catch-up token, rows that rewind ignore its write; the
        # round's k rides as traced data)
        self._draft_cache = self._draft_fixup_jit(
            dparams, dbufs, self._draft_cache, d_toks[-1], m_dev,
            self._active_dev, jnp.asarray(k, jnp.int32))
        return emitted_dev, m_dev

    def _deliver(self, host) -> None:
        """Acceptance accounting, then each slot's accepted chunk into
        ``_commit``, which cuts it at EOS and at the budget."""
        emitted, m_host = host
        self._rounds += 1
        self._drafted += self._spec_k_active * len(self._rows)
        self._accepted += int(m_host[[slot for slot, _ in self._rows]]
                              .sum())
        for slot, _ in self._rows:
            self._commit(
                slot, map(int, emitted[slot, :int(m_host[slot]) + 1]))

    def refresh_weights(self):
        """Drop BOTH models' cached weight value lists (hot swap)."""
        super().refresh_weights()
        self._draft_state_cache = None

    def _new_draft_cache(self):
        """Allocate the dense fp32 draft slot cache (placed over the
        mesh — slot axis 'dp', head axis 'mp' — when one is set)."""
        cache = self._draft_session._model.gen_decode_cache(
            self.slots, self.max_len, "float32", per_slot=True)
        if self._mesh is not None:
            cache = self._mesh.place_cache(cache)
        return cache

    def reset(self):
        """Base reset (queue/slots/target cache/allocator) plus a fresh
        draft slot cache — the draft's state is as untrusted as the
        target's after a failed round, and it rebuilds the same way:
        re-allocation only, every compiled executable kept."""
        super().reset()
        self._draft_cache = self._new_draft_cache()

    def acceptance_stats(self) -> dict:
        """{'spec_k', 'rounds', 'drafted', 'accepted',
        'acceptance_rate', 'spec_k_active'} — the rate is what the
        ``serving_acceptance_rate`` gauge reads."""
        stats = acceptance_summary(self.spec_k, self._rounds,
                                   self._drafted, self._accepted)
        stats["spec_k_active"] = self._spec_k_active
        return stats

    def reset_acceptance_stats(self) -> None:
        """Zero the acceptance accounting: a caller that warms the pool
        calls this before the traffic it wants the rate to cover."""
        self._drafted = self._accepted = self._rounds = 0

    def compile_counts(self) -> dict:
        """Base pool accounting plus the speculative executables: the
        contract is that NONE of these grow with rounds or acceptance
        lengths (pinned by tests)."""
        counts = super().compile_counts()
        # the target's 1-token steps are unused here: the verify chunk
        # IS the target's decode step
        counts.pop("decode", None)
        counts.pop("pool_decode", None)
        counts["verify"] = int(self._verify_jit._cache_size())
        counts["draft_prefill"] = int(
            self._draft_session._prefill_jit._cache_size())
        counts["draft_decode"] = int(
            self._draft_decode_jit._cache_size())
        counts["draft_fixup"] = int(self._draft_fixup_jit._cache_size())
        counts["draft_insert"] = int(
            self._draft_insert_jit._cache_size())
        return counts

    def cost_version(self) -> int:
        return (super().cost_version()
                + self._draft_session.cost_version()
                + self._verify_jit.compiles
                + self._draft_decode_jit.compiles
                + self._draft_fixup_jit.compiles
                + self._draft_insert_jit.compiles)

    def cost_report(self) -> dict:
        """Base report plus the speculative executables; the round's
        device work is ``spec_k`` draft steps + one verify + one
        fixup, so ``derived`` divides the ROUND's compiler-reported
        FLOPs/bytes over the tokens a round commits — ``slots x (1 +
        acceptance_rate x spec_k)``, using the MEASURED acceptance rate
        (worst case 1 token/slot before any round), and says so in
        ``basis`` so the per-token figure is auditable."""
        rep = super().cost_report()
        # the target's 1-token executables are unused here, exactly as
        # in compile_counts: the verify chunk IS the target's step
        rep.pop("decode", None)
        rep.pop("pool_decode", None)
        rep["verify"] = self._verify_jit.cost_report()
        rep["draft_prefill"] = \
            self._draft_session._prefill_jit.cost_report()
        rep["draft_decode"] = self._draft_decode_jit.cost_report()
        rep["draft_fixup"] = self._draft_fixup_jit.cost_report()
        rep["draft_insert"] = self._draft_insert_jit.cost_report()
        verify = self._verify_jit.last_cost()
        draft = self._draft_decode_jit.last_cost()
        fixup = self._draft_fixup_jit.last_cost()
        if not verify or "flops" not in verify or not draft \
                or "flops" not in draft:
            rep["derived"] = {}
            return rep
        acc = acceptance_summary(self.spec_k, self._rounds,
                                 self._drafted,
                                 self._accepted)["acceptance_rate"]
        fixup_flops = (fixup or {}).get("flops", 0.0)
        fixup_bytes = (fixup or {}).get("bytes_accessed", 0.0)
        # the round's HBM reservation spans TWO resident executables —
        # the verify step (target weights + target cache) and the
        # draft step (draft weights + draft cache); the fixup aliases
        # the draft step's buffers, so summing it too would double
        # count.  A speculative engine's gauge must carry the draft
        # side: reporting verify alone would under-provision exactly
        # the engines that run two models
        verify_hbm = verify.get("hbm_reserved_bytes")
        draft_hbm = draft.get("hbm_reserved_bytes")
        round_hbm = None if verify_hbm is None or draft_hbm is None \
            else verify_hbm + draft_hbm
        round_entry = {
            "flops": self.spec_k * draft["flops"] + verify["flops"]
            + fixup_flops,
            "bytes_accessed": self.spec_k * draft["bytes_accessed"]
            + verify["bytes_accessed"] + fixup_bytes,
            "hbm_reserved_bytes": round_hbm,
            "kv_cache_bytes": verify.get("kv_cache_bytes"),
        }
        rep["derived"] = self._derived_costs(
            round_entry,
            tokens_per_step_per_slot=1.0 + acc * self.spec_k,
            basis="speculative round (spec_k=%d draft steps + verify + "
                  "fixup) commits slots x (1 + acceptance_rate x "
                  "spec_k) tokens at the measured acceptance_rate=%.4f"
                  % (self.spec_k, acc))
        rep["derived"]["acceptance_rate"] = acc
        rep["derived"]["hbm_verify_bytes"] = verify_hbm
        rep["derived"]["hbm_draft_bytes"] = draft_hbm
        return rep
