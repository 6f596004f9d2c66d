"""Speculative decoding: draft/verify session + pool (docs/DESIGN.md §5e).

Pins the contracts the speculative path lives on:

- greedy speculative output is TOKEN-IDENTICAL to target-only greedy
  decode, for dense AND paged target caches, fp32 AND int8 cache
  dtypes, session and pool — over the margin-gated corpus (the same
  gating as the int8 tests: a chunk forward reduces in a different
  order than a 1-token step, so a genuine fp top-2 near-tie is a
  coin-flip no decode strategy can promise);
- the compile budget is FIXED whatever the acceptance lengths: the
  draft session compiles exactly two functions (prefill + decode, the
  catch-up step reusing the decode executable), the target compiles
  its prefill bucket(s) plus ONE verify step — acceptance length is
  data, never a shape;
- an EOS inside an ACCEPTED chunk truncates the commit AT the EOS
  (``truncate_at_eos``) — the accepted tail and bonus token behind it
  are never emitted;
- rejection rewinds by moving the cache index pointer: paged
  cancellation still returns every block, slot churn stays leak-free;
- construction fails with typed errors for a draft/target vocab
  mismatch (naming both sizes), non-greedy sampling configs, and a
  speculative session without K tokens of cache headroom;
- the ServingEngine schedules speculative slots through its unchanged
  lifecycle and gains only the ``serving_acceptance_rate`` gauge.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool, SpeculativePool
from paddle_tpu.jit import (DecodeSession, SpeculativeDecodeSession,
                            truncate_at_eos)
from paddle_tpu.jit.decode import FINISH_EOS
from paddle_tpu.models import TransformerLM
from paddle_tpu.serving import RequestState, ServingEngine


def _tiny_model(vocab=128, hidden=64, heads=4, layers=2, seed=0,
                max_position=1024):
    pt.seed(seed)
    return TransformerLM(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=2 * hidden,
        max_position=max_position, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def target():
    return _tiny_model()


@pytest.fixture(scope="module")
def draft():
    # a REAL draft: different (smaller) geometry, independent init —
    # its guesses are mostly wrong on random weights, which exercises
    # the rejection/rewind path hard; the self-draft cases exercise the
    # all-accepted/catch-up path
    return _tiny_model(hidden=32, layers=1, seed=1)


# the same margin discipline as tests/test_quant_cache.py: the verify
# chunk reduces attention in a different order than the 1-token step
# (and int8 adds quantization noise), so prompts whose fp32 top-2
# decision margin sits under the noise floor at any step are genuine
# coin-flips and are excluded; everything above must match exactly
_MARGIN_FLOOR = 5e-3


def _greedy_with_margin(model, sess, ids, gen):
    """(reference greedy tokens from ``sess``, min top-2 fp32 logit
    margin over every emitting decision — read from one uncached full
    forward, which causality makes per-position identical to what each
    greedy step saw)."""
    got = sess.generate(ids, gen)
    full_seq = np.concatenate([np.asarray(ids), got], axis=1)
    logits = np.asarray(model(pt.to_tensor(full_seq)).value)
    steps = logits[:, ids.shape[1] - 1:-1]
    top2 = np.sort(steps, axis=-1)[..., -2:]
    return got, float((top2[..., 1] - top2[..., 0]).min())


def _gated_corpus(model, sess, gen, seeds, min_prompts=3):
    """[(prompt 1-D, want 1-D)] margin-gated prompts with their
    reference generations from ``sess`` (the target-only baseline the
    speculative output must reproduce token-for-token)."""
    out = []
    for seed in seeds:
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, 128,
                          (1, int(rng.randint(3, 13)))).astype("int32")
        want, margin = _greedy_with_margin(model, sess, ids, gen)
        if margin >= _MARGIN_FLOOR:
            out.append((ids[0], want[0]))
    assert len(out) >= min_prompts, \
        "corpus too thin: only %d prompts cleared the margin" % len(out)
    return out


# -- the acceptance contract: token identity, session ---------------------

@pytest.mark.parametrize("layout_kw", [
    pytest.param({}, id="dense"),
    pytest.param(dict(cache_layout="paged", block_size=8), id="paged"),
])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_session_greedy_token_identical(target, draft, layout_kw, dtype):
    ref = DecodeSession(target, max_len=64, buckets=[16],
                        cache_dtype=dtype, **layout_kw)
    spec = SpeculativeDecodeSession(target, draft, max_len=64, spec_k=3,
                                    buckets=[16], cache_dtype=dtype,
                                    **layout_kw)
    spec_self = SpeculativeDecodeSession(target, target, max_len=64,
                                         spec_k=3, buckets=[16],
                                         cache_dtype=dtype, **layout_kw)
    for prompt, want in _gated_corpus(target, ref, 8, range(6)):
        np.testing.assert_array_equal(
            spec.generate(prompt[None], 8)[0], want,
            err_msg="small draft, %s %s" % (layout_kw, dtype))
        np.testing.assert_array_equal(
            spec_self.generate(prompt[None], 8)[0], want,
            err_msg="self draft, %s %s" % (layout_kw, dtype))
    # a self-draft's guesses are the target's own greedy continuations:
    # near-total acceptance, exercising the bonus-token/catch-up path
    assert spec_self.acceptance_stats()["acceptance_rate"] > 0.9
    st = spec.acceptance_stats()
    assert st["drafted"] == st["spec_k"] * st["rounds"]
    assert 0.0 <= st["acceptance_rate"] <= 1.0


def test_session_compile_counts_fixed(target, draft):
    # THE compile-budget contract: rounds with every acceptance length
    # (self-draft ~all accepted, small draft ~all rejected) and varying
    # prompt lengths within a bucket share the same four executables;
    # only a NEW BUCKET adds a (prefill) compilation
    spec = SpeculativeDecodeSession(target, draft, max_len=64, spec_k=3,
                                    buckets=[8, 16])
    rng = np.random.RandomState(0)
    for length in (4, 6, 7):
        spec.generate(rng.randint(0, 128, (1, length)).astype("int32"),
                      8)
    assert spec.compile_counts() == {
        "prefill": 1, "verify": 1, "draft_prefill": 1, "draft_decode": 1}
    spec.generate(rng.randint(0, 128, (1, 12)).astype("int32"), 8)
    assert spec.compile_counts() == {
        "prefill": 2, "verify": 1, "draft_prefill": 2, "draft_decode": 1}
    # the all-accepted path (catch-up step) must reuse the same
    # executables too
    spec_self = SpeculativeDecodeSession(target, target, max_len=64,
                                         spec_k=3, buckets=[16])
    spec_self.generate(rng.randint(0, 128, (1, 5)).astype("int32"), 10)
    assert spec_self.compile_counts() == {
        "prefill": 1, "verify": 1, "draft_prefill": 1, "draft_decode": 1}


def test_session_eos_inside_accepted_chunk_truncates(target):
    # self-draft: whole chunks are accepted, so an EOS landing mid-chunk
    # pins the truncate-at-EOS commit rule (the accepted tail and the
    # bonus token behind the EOS must never be emitted)
    ref = DecodeSession(target, max_len=64, buckets=[16])
    spec = SpeculativeDecodeSession(target, target, max_len=64,
                                    spec_k=4, buckets=[16])
    rng = np.random.RandomState(3)
    ids = rng.randint(0, 128, (1, 6)).astype("int32")
    full = ref.generate(ids, 10)
    # token index 3 sits INSIDE the first verify chunk (the prefill
    # emits token 0; the chunk commits tokens 1..5 on full acceptance)
    eos = int(full[0, 3])
    first = int(np.argmax(full[0] == eos))  # first occurrence governs
    got = spec.generate(ids, 10, eos_id=eos)
    assert got.shape == (1, 10)
    np.testing.assert_array_equal(got[0, :first + 1],
                                  full[0, :first + 1])
    assert (got[0, first + 1:] == eos).all(), got


def test_truncate_at_eos_edge_cases():
    # the commit rule itself: first EOS wins, inclusive; no EOS or no
    # eos_id passes through; empty stays empty; a leading EOS cuts to
    # one token (the classify_finish vocabulary then reads EOS for
    # every truncated result because it always ends on the EOS)
    from paddle_tpu.jit.decode import classify_finish

    np.testing.assert_array_equal(truncate_at_eos([4, 7, 2, 9], 2),
                                  [4, 7, 2])
    np.testing.assert_array_equal(truncate_at_eos([2, 7, 2, 9], 2), [2])
    np.testing.assert_array_equal(truncate_at_eos([4, 7, 9], 2),
                                  [4, 7, 9])
    np.testing.assert_array_equal(truncate_at_eos([4, 7], None), [4, 7])
    assert truncate_at_eos([], 2).size == 0
    assert classify_finish(truncate_at_eos([4, 2, 5], 2), 2) == FINISH_EOS


# -- construction-time validation -----------------------------------------

def test_vocab_mismatch_typed_error_names_both_sizes(target):
    small_vocab = _tiny_model(vocab=96, hidden=32, layers=1, seed=2)
    with pytest.raises(InvalidArgumentError, match="96.*128|128.*96"):
        SpeculativeDecodeSession(target, small_vocab, max_len=64,
                                 buckets=[16])
    with pytest.raises(InvalidArgumentError, match="96.*128|128.*96"):
        SpeculativePool(target, small_vocab, max_len=64, slots=2,
                        buckets=[16])


def test_greedy_only_and_spec_k_validated(target, draft):
    with pytest.raises(InvalidArgumentError, match="greedy"):
        SpeculativeDecodeSession(target, draft, max_len=64,
                                 buckets=[16], temperature=0.7)
    with pytest.raises(InvalidArgumentError, match="greedy"):
        SpeculativePool(target, draft, max_len=64, slots=2,
                        buckets=[16], temperature=0.7)
    with pytest.raises(InvalidArgumentError, match="spec_k"):
        SpeculativeDecodeSession(target, draft, max_len=64,
                                 buckets=[16], spec_k=0)
    # top_k/top_p ride ServingEngine's **pool_kwargs on the plain pool
    # (ignored at temperature=0); the speculative swap must stay a
    # drop-in, not die on an untyped TypeError
    SpeculativePool(target, draft, max_len=64, slots=2, buckets=[16],
                    top_k=5, top_p=0.9)
    # spec_k without a draft must not silently run un-speculated
    with pytest.raises(InvalidArgumentError, match="draft_model"):
        ServingEngine(target, max_len=64, slots=2, buckets=[16],
                      spec_k=4)


def test_session_headroom_and_batch_validated(target, draft):
    spec = SpeculativeDecodeSession(target, draft, max_len=32, spec_k=4,
                                    buckets=[16])
    # 10 + 20 fits a plain session's max_len=32... except the verify
    # chunk can write spec_k past the budget: typed error names the K
    with pytest.raises(InvalidArgumentError, match="spec_k"):
        spec.generate(np.zeros((1, 10), np.int32), 20)
    with pytest.raises(InvalidArgumentError, match="SpeculativePool"):
        spec.generate(np.zeros((2, 4), np.int32), 4)


# -- the pool variant -----------------------------------------------------

@pytest.mark.parametrize("layout_kw", [
    pytest.param({}, id="dense"),
    pytest.param(dict(cache_layout="paged", block_size=8), id="paged"),
])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_pool_token_identical_with_refill(target, draft, layout_kw,
                                          dtype):
    # more margin-gated requests than slots: the speculative rounds run
    # through slot refill/churn and must still reproduce the target-only
    # session token-for-token
    ref = DecodeSession(target, max_len=64, buckets=[16],
                        cache_dtype=dtype, **layout_kw)
    corpus = _gated_corpus(target, ref, 6, range(20, 28))
    pool = SpeculativePool(target, draft, max_len=64, spec_k=3, slots=2,
                           buckets=[16], cache_dtype=dtype, **layout_kw)
    outs = pool.generate([p for p, _ in corpus], 6)
    for (prompt, want), got in zip(corpus, outs):
        np.testing.assert_array_equal(got, want,
                                      err_msg=str((layout_kw, dtype)))
    counts = pool.compile_counts()
    assert counts == {"prefill": 1, "slot_insert": 1, "verify": 1,
                      "draft_prefill": 1, "draft_decode": 1,
                      "draft_fixup": 1, "draft_insert": 1}, counts


def test_pool_self_draft_commits_chunks(target):
    # self-draft: every round commits spec_k+1 tokens per slot, so the
    # round count collapses from ~gen to ~gen/(spec_k+1) — the
    # amortization the whole design exists for, observable in the stats
    ref = DecodeSession(target, max_len=64, buckets=[16])
    corpus = _gated_corpus(target, ref, 12, range(40, 46), min_prompts=2)
    pool = SpeculativePool(target, target, max_len=64, spec_k=3,
                           slots=2, buckets=[16])
    outs = pool.generate([p for p, _ in corpus], 12)
    for (prompt, want), got in zip(corpus, outs):
        np.testing.assert_array_equal(got, want)
    st = pool.acceptance_stats()
    assert st["acceptance_rate"] > 0.9
    # 12 tokens = 1 prefill token + ceil(11/4) fully-accepted rounds
    assert st["rounds"] <= 4 * len(corpus)


def test_pool_eos_mid_chunk_truncates_and_classifies(target):
    ref = DecodeSession(target, max_len=64, buckets=[16])
    rng = np.random.RandomState(7)
    ids = rng.randint(0, 128, (6,)).astype("int32")
    full = ref.generate(ids[None], 10)[0]
    eos = int(full[2])  # inside the first accepted chunk
    first = int(np.argmax(full == eos))
    pool = SpeculativePool(target, target, max_len=64, spec_k=4,
                           slots=1, buckets=[16], eos_id=eos)
    rid = pool.submit(ids, 10)
    while pool.step():
        pass
    tokens, reason = pool.collect(rid)
    # committed tokens STOP at the EOS: the accepted tail behind it was
    # truncated, not emitted
    np.testing.assert_array_equal(tokens, full[:first + 1])
    assert reason == FINISH_EOS


def test_pool_cancel_mid_round_frees_blocks(target, draft):
    ref = DecodeSession(target, max_len=64, buckets=[16])
    corpus = _gated_corpus(target, ref, 6, range(60, 66), min_prompts=2)
    (pa, _), (pb, want_b) = corpus[0], corpus[1]
    pool = SpeculativePool(target, draft, max_len=64, spec_k=3, slots=2,
                           buckets=[16], cache_layout="paged",
                           block_size=8)
    free0 = len(pool._free_blocks)
    ra = pool.submit(pa, 20)
    rb = pool.submit(pb, 6)
    pool.step()
    assert pool.cancel(ra) == "active"
    results = pool.run()
    assert set(results) == {rb}
    # the survivor decoded through the churned allocator unharmed, and
    # every paged block came back
    np.testing.assert_array_equal(results[rb], want_b)
    assert len(pool._free_blocks) == free0


# -- under the serving engine ---------------------------------------------

def test_engine_speculative_token_identical_and_acceptance_gauge(
        target):
    ref = DecodeSession(target, max_len=64, buckets=[16])
    corpus = _gated_corpus(target, ref, 6, range(80, 88), min_prompts=3)
    plain = ServingEngine(target, max_len=64, slots=2, buckets=[16])
    eng = ServingEngine(target, max_len=64, slots=2, buckets=[16],
                        draft_model=target, spec_k=3)
    for prompt, want in corpus:
        got = np.asarray(list(eng.submit(prompt, 6)), np.int32)
        np.testing.assert_array_equal(got, want)
    # the scheduler is UNCHANGED: lifecycle states, stream status and
    # finish reasons ride the speculative pool verbatim
    st = eng.submit(corpus[0][0], 6).result(timeout_s=None)
    assert st.state == RequestState.DONE
    assert st.new_tokens == 6
    snap = eng.metrics.snapshot()
    assert snap["serving_acceptance_rate"] > 0.9  # self-draft
    assert "serving_acceptance_rate" in eng.metrics.render_prometheus()
    assert eng.acceptance_stats()["drafted"] > 0
    # a plain engine carries neither the gauge nor the stats
    assert "serving_acceptance_rate" not in plain.metrics.snapshot()
    assert plain.acceptance_stats() is None
    counts = eng.compile_counts()
    assert counts["verify"] == 1 and counts["draft_decode"] == 1


def test_engine_speculative_deadline_expiry_frees_slot(target, draft):
    from tests.test_serving import FakeClock

    clock = FakeClock()
    eng = ServingEngine(target, max_len=64, slots=1, buckets=[16],
                        draft_model=draft, spec_k=3,
                        cache_layout="paged", block_size=8, clock=clock)
    baseline = eng.cache_stats()["free_blocks"]
    a = eng.submit(np.zeros(5, np.int32), 40, deadline_s=1.0)
    eng.pump(2)
    assert eng.request_state(a.request_id) == RequestState.DECODING
    clock.advance(2.0)
    eng.pump(1)
    st = a.result(timeout_s=0)
    assert st.state == RequestState.EXPIRED
    assert 0 < st.new_tokens < 40
    assert eng.cache_stats()["free_blocks"] == baseline
