"""Speculative decoding: the draft/verify split over the decode engine.

Decode throughput is bounded by one target-model dispatch per emitted
token; speculative decoding amortizes that by letting a SMALL draft
model guess K tokens cheaply and having the target model judge all of
them in ONE prefill-shaped chunk forward — the structure the
prefill/decode split (docs/DESIGN.md §5a) already exposes:

- **draft** = the draft model's ordinary compiled decode step, run K
  times (``DecodeSession`` reused verbatim: exactly two compiled
  functions, prefill + decode);
- **verify** = one fixed-shape ``[1, K+1]`` chunk forward of the target
  through its decode cache (the multi-token append of
  ``_decode_forward`` / ``_paged_decode_forward``), compiled ONCE — the
  acceptance length is data, never a shape, so there are no
  per-acceptance-length recompiles (rejected tail positions are padded
  and masked by the cache index, the same compiler-first discipline as
  the bucketed prefill).

Greedy acceptance: the chunk ``[pending, d_1..d_K]`` yields target
greedy continuations ``g_0..g_K``; drafts are accepted while
``d_i == g_{i-1}``, then the target's own ``g_m`` is emitted as the
correction (or the bonus token when everything matched).  Every emitted
token is therefore EXACTLY what target-only greedy decode would have
produced — speculation changes the COST per token, never the tokens.

Rejection rewinds by MOVING THE CACHE INDEX POINTER: the rejected
drafts' K/V stay in the buffer as stale rows past the index (never
attended, overwritten by the next chunk), for both cache layouts and
both cache dtypes — the paged layout's rejected writes land through the
block table with the same scratch-block masking as slot churn, and the
int8 layout's per-position scales rewind with their values for free
(a position's scale is fixed at its write).

``SpeculativeDecodeSession`` is the single-request unit (batch 1 — with
an aligned batch every row would stall on the slowest acceptance);
``inference.SpeculativePool`` is the slot-batched serving variant whose
per-row index vector lets every slot accept a different prefix length.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError
from . import aot
from .decode import DecodeSession, truncate_at_eos

__all__ = ["SpeculativeDecodeSession", "check_draft_compatible",
           "model_vocab_size", "greedy_accept", "acceptance_summary"]


def model_vocab_size(model) -> Optional[int]:
    """The model's token id space, from ``vocab_size`` or the word
    embedding table; None when neither is discoverable."""
    v = getattr(model, "vocab_size", None)
    if v is None:
        w = getattr(getattr(model, "word_embeddings", None), "weight",
                    None)
        v = None if w is None else int(w.shape[0])
    return None if v is None else int(v)


def check_draft_compatible(draft_model, target_model) -> None:
    """Typed error unless draft and target share one token id space —
    checked at CONSTRUCTION (session and pool), because a vocab
    mismatch would otherwise surface as a shape error inside the first
    verify trace, or worse: decode silently with ids that mean
    different strings under the two models."""
    dv = model_vocab_size(draft_model)
    tv = model_vocab_size(target_model)
    if dv is not None and tv is not None and dv != tv:
        raise InvalidArgumentError(
            "speculative decoding needs the draft and target models to "
            "share one token id space: draft vocab_size=%d != target "
            "vocab_size=%d — a draft token id would name a different "
            "string under the target" % (dv, tv))


def greedy_accept(logits, chunk, active=None):
    """The greedy acceptance rule, trace-level and SHARED by the
    session and ``inference.SpeculativePool`` (one place to change
    when the rejection-sampling variant lands): given the target's
    ``logits`` [B, K+1, V] over a verify chunk ``[pending, d_1..d_K]``,
    return ``(m [B], emitted [B, K+1])`` — the accepted-prefix lengths
    (drafts accepted while ``d_i == g_{i-1}``, cumprod zeroes
    everything after the first mismatch) and the emission
    (``d_1..d_m`` then the target's own correction-or-bonus ``g_m``,
    pad past it).  ``active`` [B] bool, when given, zeroes inactive
    rows' ``m`` and emission (the pool's frozen slots)."""
    k = chunk.shape[1] - 1
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B, K+1]
    draft = chunk[:, 1:]
    match = (draft == g[:, :-1]).astype(jnp.int32)
    m = jnp.cumprod(match, axis=1).sum(axis=1)           # [B]
    if active is not None:
        m = jnp.where(active, m, 0)
    j = jnp.arange(k + 1)[None, :]
    g_at_m = jnp.take_along_axis(g, m[:, None], axis=1)
    draft_pad = jnp.concatenate(
        [draft, jnp.zeros_like(draft[:, :1])], axis=1)
    emitted = jnp.where(j < m[:, None], draft_pad,
                        jnp.where(j == m[:, None], g_at_m, 0))
    if active is not None:
        emitted = jnp.where(active[:, None], emitted, 0)
    return m, emitted


def acceptance_summary(spec_k: int, rounds: int, drafted: int,
                       accepted: int) -> dict:
    """The shared ``acceptance_stats()`` record: {'spec_k', 'rounds',
    'drafted', 'accepted', 'acceptance_rate'} — accepted draft tokens /
    drafted, what the ``serving_acceptance_rate`` gauge reads (0.0
    before any round)."""
    return {
        "spec_k": spec_k,
        "rounds": rounds,
        "drafted": drafted,
        "accepted": accepted,
        "acceptance_rate": accepted / drafted if drafted else 0.0,
    }


class SpeculativeDecodeSession:
    """Single-request speculative generation with a FIXED compile
    budget: exactly two compiled functions for the draft (its
    ``DecodeSession`` prefill + decode step) and, for the target, one
    prefill per bucket plus ONE fixed-K verify step.

    Greedy only (``temperature`` must be 0): distribution-preserving
    speculative SAMPLING needs the rejection-sampling acceptance rule,
    which is future work; greedy acceptance is exact by construction.

    ``cache_layout``/``cache_dtype`` configure the TARGET cache (the
    one whose HBM matters); the draft — small by design — keeps a dense
    fp32 cache, where the paged/int8 machinery would add complexity
    without touching the bandwidth bill.
    """

    def __init__(self, target_model, draft_model, max_len: int,
                 spec_k: int = 4, buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, cache_dtype="float32",
                 cache_layout: str = "dense", block_size: int = 32,
                 donate: Optional[bool] = None, route: str = "auto"):
        if float(temperature) != 0.0:
            raise InvalidArgumentError(
                "speculative decoding is greedy-only (temperature=0): "
                "got temperature=%r; sampled speculation needs the "
                "rejection-sampling acceptance rule to preserve the "
                "target distribution — use DecodeSession for sampled "
                "generation" % (temperature,))
        if int(spec_k) < 1:
            raise InvalidArgumentError(
                "spec_k must be >= 1 draft tokens per round, got %r"
                % (spec_k,))
        check_draft_compatible(draft_model, target_model)
        self.spec_k = int(spec_k)
        # the route reaches the verify chunk through the target
        # session's _run_model (§5l): Lq = spec_k+1 <= 8 keeps the
        # verify inside the fused kernel's chunk window
        self._target = DecodeSession(
            target_model, max_len, buckets=buckets, temperature=0.0,
            cache_dtype=cache_dtype, donate=donate,
            cache_layout=cache_layout, block_size=block_size,
            route=route)
        if self._target._layout.windowed:
            raise InvalidArgumentError(
                "speculative decoding does not support cache_layout=%r: a "
                "verify chunk of several positions that starts mid-way "
                "may ask a window entry for keys its ring has already "
                "overwritten, and a rewind cannot bring them back (the "
                "windowed kernel takes one query a row)" % (self._target._layout.name,))
        if not self._target._layout.positional:
            raise InvalidArgumentError(
                "speculative decoding does not support cache_layout=%r "
                "(%s): verify-rewind moves a positional index back over "
                "rejected drafts, and a recurrent state has no earlier "
                "position to go back to"
                % (self._target._layout.name,
                   self._target._layout.recurrent_entries()))
        self._draft = DecodeSession(
            draft_model, max_len, buckets=buckets, temperature=0.0,
            donate=donate, route=route)
        self.max_len = self._target.max_len
        self.cache_layout = cache_layout
        if donate is None:
            donate = jax.default_backend() != "cpu"
        # argnum 2 = the target cache: the verify step consumes its
        # input cache and returns the successor (index rewound in-trace)
        self._verify_jit = jax.jit(self._verify,
                                   donate_argnums=(2,) if donate else ())
        # AOT routing (jit.aot): the fixed-K verify chunk keys the one
        # verify executable; its entry carries the target cache's
        # kv_cache_bytes like every decode-family step
        self._verify_jit = aot.AotFunction(
            self._verify_jit,
            key_fn=lambda p, b, cache, chunk: aot.shape_key(chunk),
            name="verify",
            meta_fn=lambda p, b, cache, *r: {
                "kv_cache_bytes": aot.kv_arg_bytes(cache)})
        self._drafted = 0
        self._accepted = 0
        self._rounds = 0

    # -- traced body -----------------------------------------------------
    def _verify(self, param_vals, buf_vals, cache, chunk):
        """One fixed-shape verify step: chunk ``[1, K+1]`` =
        ``[pending, d_1..d_K]`` through the target's cached forward.
        Returns (cache with the index REWOUND to the accepted prefix,
        emitted tokens ``[1, K+1]`` — positions past ``m`` are pad —
        and the accepted-draft count ``m``).

        The chunk append writes all K+1 positions' K/V; acceptance only
        moves the index, so the rejected tail becomes stale rows the
        next chunk overwrites — no shape depends on ``m``, hence no
        recompile ever."""
        sess = self._target
        idx0 = cache[0].index
        logits, cache = sess._run_model(param_vals, buf_vals, chunk,
                                        cache)
        m, emitted = greedy_accept(logits, chunk)           # [1], [1,K+1]
        cache = [c._replace(index=idx0 + m[0] + 1) for c in cache]
        return cache, emitted, m[0]

    # -- host API --------------------------------------------------------
    def generate(self, input_ids, max_new_tokens: int, seed=None,
                 eos_id: Optional[int] = None):
        """Greedy speculative generation; np.int32 ``[1, max_new_tokens]``
        token-identical to ``DecodeSession.generate`` on the target
        alone (the draft only changes how many target dispatches the
        tokens cost).  EOS semantics match the plain session: rows past
        their EOS are padded with it — and an EOS inside an ACCEPTED
        chunk truncates the commit at the EOS (``truncate_at_eos``),
        never emitting the accepted tail behind it."""
        ids = np.asarray(getattr(input_ids, "value", input_ids))
        if ids.ndim == 1:
            ids = ids[None]
        if ids.shape[0] != 1:
            raise InvalidArgumentError(
                "SpeculativeDecodeSession generates ONE request at a "
                "time (got batch %d): aligned speculative batches would "
                "stall every row on the slowest acceptance; use "
                "inference.SpeculativePool for slot-batched speculative "
                "serving" % (ids.shape[0],))
        t = ids.shape[1]
        if max_new_tokens < 1:
            raise InvalidArgumentError(
                "max_new_tokens must be >= 1, got %r" % (max_new_tokens,))
        k = self.spec_k
        if t + max_new_tokens + k > self.max_len:
            # the final verify chunk may write up to K draft positions
            # past the last budgeted token; without headroom the
            # shape-static chunk write would CLAMP onto valid rows
            raise InvalidArgumentError(
                "speculative decoding writes up to spec_k=%d draft "
                "positions past the accepted prefix: prompt %d + "
                "max_new_tokens %d + spec_k %d exceeds cache max_len %d;"
                " raise max_len or lower max_new_tokens/spec_k"
                % (k, t, max_new_tokens, k, self.max_len))
        # greedy-only session: the as-data sampling states are all-zero
        # temperature vectors (``seed`` is accepted for signature parity
        # but greedy never draws), threaded through prefill/decode in
        # the key position the compiled signatures expect
        del seed
        cache_t, tok, _samp_t = self._target.prefill(
            ids, self._target.sampling_state(1, temperature=0.0))
        # the draft prefills the SAME prompt; its sampled token is
        # discarded — the target's first token is the ground truth the
        # draft must continue from
        samp_d = self._draft.sampling_state(1, temperature=0.0)
        cache_d, _tok_d, samp_d = self._draft.prefill(ids, samp_d)
        params_t, bufs_t = self._target._state_vals()
        params_d, bufs_d = self._draft._state_vals()
        toks = [int(np.asarray(tok)[0])]
        done = eos_id is not None and toks[0] == int(eos_id)
        pending = jnp.asarray(np.array([toks[0]], np.int32))
        while len(toks) < max_new_tokens and not done:
            # draft K greedy steps (the draft's own compiled step)
            d_toks = []
            tk = pending
            for _ in range(k):
                cache_d, tk, samp_d = self._draft._decode_jit(
                    params_d, bufs_d, cache_d, tk, samp_d)
                d_toks.append(tk)
            chunk = jnp.concatenate(
                [pending[:, None]] + [x[:, None] for x in d_toks],
                axis=1)
            cache_t, emitted, m = self._verify_jit(params_t, bufs_t,
                                                   cache_t, chunk)
            m_h = int(m)
            self._drafted += k
            self._accepted += m_h
            self._rounds += 1
            # committed cache length must end up at t+len(toks)-1+m+1
            # (the last emitted token stays PENDING, not yet written)
            new_draft_idx = t + len(toks) - 1 + m_h + 1
            if m_h == k:
                # everything accepted: the draft never wrote d_K's K/V
                # (d_K was its pending output) — one catch-up step of
                # the SAME compiled executable writes it; the sampled
                # token is discarded
                cache_d, _tk, samp_d = self._draft._decode_jit(
                    params_d, bufs_d, cache_d, d_toks[-1], samp_d)
            else:
                # rejection rewind: move the index pointer; the stale
                # draft rows are overwritten before they could ever be
                # attended (same contract as the target cache)
                idx = jnp.asarray(new_draft_idx, jnp.int32)
                cache_d = [c._replace(index=idx) for c in cache_d]
            emitted_h = np.asarray(emitted)[0, :m_h + 1].astype(np.int32)
            take = truncate_at_eos(
                emitted_h[:max_new_tokens - len(toks)], eos_id)
            toks.extend(int(x) for x in take)
            if eos_id is not None and take.size and \
                    int(take[-1]) == int(eos_id):
                done = True
            elif take.size < m_h + 1:
                break  # budget exhausted mid-chunk
            else:
                pending = jnp.asarray(np.array([toks[-1]], np.int32))
        out = np.asarray(toks, np.int32)[None]
        if out.shape[1] < max_new_tokens:
            pad = np.full((1, max_new_tokens - out.shape[1]),
                          eos_id, np.int32)
            out = np.concatenate([out, pad], axis=1)
        return out

    def acceptance_stats(self) -> dict:
        """The shared :func:`acceptance_summary` record."""
        return acceptance_summary(self.spec_k, self._rounds,
                                  self._drafted, self._accepted)

    def compile_counts(self) -> dict:
        """The compile-budget contract, observable: the draft is its
        DecodeSession's exactly-two (prefill bucket + decode step, the
        catch-up step reusing the decode executable); the target is its
        prefill bucket(s) plus ONE verify step whatever the acceptance
        lengths seen."""
        return {
            "prefill": int(self._target._prefill_jit._cache_size()),
            "verify": int(self._verify_jit._cache_size()),
            "draft_prefill": int(self._draft._prefill_jit._cache_size()),
            "draft_decode": int(self._draft._decode_jit._cache_size()),
        }

    def cost_report(self) -> dict:
        """Per-executable cost/memory attribution (``jit.aot``) for the
        session's fixed compile budget: target prefill bucket(s) + the
        one verify step, draft prefill + decode — read off the compiled
        artifacts, never a compile."""
        return {
            "prefill": self._target._prefill_jit.cost_report(),
            "verify": self._verify_jit.cost_report(),
            "draft_prefill": self._draft._prefill_jit.cost_report(),
            "draft_decode": self._draft._decode_jit.cost_report(),
        }

    def cost_version(self) -> int:
        return (self._target.cost_version() + self._draft.cost_version()
                + self._verify_jit.compiles)
