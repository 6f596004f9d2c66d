"""KV-cached decode engine (jit.DecodeSession + inference.GenerationPool).

Pins the four contracts the serving path lives on:

- cached logits == full-forward logits (the cache changes COST, never
  math);
- greedy generation is token-identical to the uncached argmax loop while
  compiling exactly TWO XLA programs (one prefill bucket + one decode
  step) for a 512-prefill / 128-token generation;
- prefill recompiles once per BUCKET, never per prompt length;
- GenerationPool's slot-batched continuous batching reproduces the
  per-request sequential results for mixed-length requests, including
  slot refill from the queue.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool, create_generation_pool
from paddle_tpu.jit import DecodeSession
from paddle_tpu.jit.decode import (default_buckets, sample_logits,
                                   sample_logits_data)
from paddle_tpu.models import TransformerLM


def _tiny_model(vocab=128, hidden=64, heads=4, layers=2, max_position=1024):
    pt.seed(0)
    return TransformerLM(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=2 * hidden,
        max_position=max_position, causal=True, dropout=0.0)


def _greedy_uncached(model, ids, n):
    """The baseline the engine must reproduce: full re-forward + argmax."""
    cur = np.asarray(ids)
    out = []
    for _ in range(n):
        logits = np.asarray(model(pt.to_tensor(cur)).value)
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        out.append(nxt)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    return np.stack(out, axis=1)


def test_cached_logits_match_full_forward():
    # chunked prefill + 1-token decode steps must reproduce the full
    # causal forward's logits (atol chosen to survive bf16 reductions)
    m = _tiny_model()
    m.eval()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (2, 10)).astype("int32")
    full = np.asarray(m(pt.to_tensor(ids)).value)
    cache = m.gen_decode_cache(2, 32)
    logits, cache = m(pt.to_tensor(ids[:, :7]), cache=cache)
    parts = [np.asarray(logits.value)]
    for t in range(7, 10):
        lg, cache = m(pt.to_tensor(ids[:, t:t + 1]), cache=cache)
        parts.append(np.asarray(lg.value))
    np.testing.assert_allclose(np.concatenate(parts, axis=1), full,
                               atol=2e-4, rtol=2e-3)


def test_greedy_matches_uncached_argmax_loop():
    # the engine vs the literal uncached loop (small case; the 512/128
    # acceptance case below uses the single-forward equivalence check)
    m = _tiny_model()
    sess = DecodeSession(m, max_len=32, buckets=[16])
    rng = np.random.RandomState(8)
    ids = rng.randint(0, 128, (2, 10)).astype("int32")
    np.testing.assert_array_equal(sess.generate(ids, 4),
                                  _greedy_uncached(m, ids, 4))


def test_greedy_token_identical_512_prefill_two_compiles():
    # THE acceptance contract: 512-token prefill + 128 generated, greedy
    # output token-identical to the uncached full-forward argmax loop,
    # with exactly one prefill-bucket compilation and one decode-step
    # compilation
    m = _tiny_model(vocab=256, hidden=32, heads=2)
    sess = DecodeSession(m, max_len=512 + 128, buckets=[512])
    rng = np.random.RandomState(1)
    ids = rng.randint(0, 256, (1, 512)).astype("int32")
    got = sess.generate(ids, 128)
    assert got.shape == (1, 128)
    assert sess.compile_counts() == {"prefill": 1, "decode": 1}
    # Token-identity with the uncached argmax loop via ONE uncached
    # forward (the loop itself re-forwards 128 times — 5 min of test
    # budget): causality makes logits[:, t] of the full 640-token
    # forward equal to what the uncached loop sees on the same prefix,
    # so at the FIRST step where the loop would diverge from `got`, the
    # loop's prefix still equals ours and the check below fails at
    # exactly that position.  No divergence anywhere == token-identical.
    full_seq = np.concatenate([ids, got], axis=1)
    logits = np.asarray(m(pt.to_tensor(full_seq)).value)
    want = logits[:, 511:-1].argmax(-1).astype(np.int32)
    np.testing.assert_array_equal(got, want)
    # a second request re-uses both executables: still exactly two
    sess.generate(ids, 4)
    assert sess.compile_counts() == {"prefill": 1, "decode": 1}


def test_bucketed_prefill_compile_count():
    # lengths 5 and 7 share the 16-bucket (ONE compile); length 20 takes
    # the 32-bucket (a second); decode stays at one compile throughout
    m = _tiny_model()
    sess = DecodeSession(m, max_len=64, buckets=[16, 32])
    rng = np.random.RandomState(2)
    for length, want_prefill in ((5, 1), (7, 1), (20, 2)):
        ids = rng.randint(0, 128, (1, length)).astype("int32")
        sess.generate(ids, 3)
        counts = sess.compile_counts()
        assert counts["prefill"] == want_prefill, (length, counts)
        assert counts["decode"] == 1, (length, counts)


def test_greedy_deterministic_and_sampling_seeded():
    m = _tiny_model()
    rng = np.random.RandomState(3)
    ids = rng.randint(0, 128, (2, 9)).astype("int32")
    sess = DecodeSession(m, max_len=64, buckets=[16])
    a, b = sess.generate(ids, 6), sess.generate(ids, 6)
    np.testing.assert_array_equal(a, b)  # greedy: key-independent
    samp = DecodeSession(m, max_len=64, buckets=[16], temperature=0.7,
                         top_k=20, top_p=0.95)
    s1, s2 = samp.generate(ids, 6, seed=11), samp.generate(ids, 6, seed=11)
    np.testing.assert_array_equal(s1, s2)  # fixed PRNG key: reproducible
    s3 = samp.generate(ids, 6, seed=12)
    assert not np.array_equal(s1, s3)  # and the key actually matters


def test_sample_logits_limits():
    import jax

    logits = np.log(np.array([[0.05, 0.6, 0.3, 0.05]], np.float32))
    key = jax.random.PRNGKey(0)
    # temperature 0 == argmax
    assert int(sample_logits(logits, key, 0.0)[0]) == 1
    # top_k=1 collapses to argmax whatever the key
    for s in range(4):
        assert int(sample_logits(logits, jax.random.PRNGKey(s), 1.0,
                                 top_k=1)[0]) == 1
    # tiny top_p keeps only the head of the distribution
    for s in range(4):
        assert int(sample_logits(logits, jax.random.PRNGKey(s), 1.0,
                                 top_p=0.1)[0]) == 1


def test_sample_logits_filtering_invariants_under_jit():
    """The filtering contracts hold INSIDE a compiled step (where the
    engine runs them): top-k keeps exactly the k highest-logit
    candidates, top-p never drops the argmax, temperature 0 is argmax."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(10)
    logits = rng.randn(1, 32).astype(np.float32) * 3.0

    # temperature 0 == argmax under jit, key-independent
    greedy = jax.jit(lambda l, k: sample_logits(l, k, 0.0))
    for s in range(3):
        assert int(greedy(jnp.asarray(logits),
                          jax.random.PRNGKey(s))[0]) == logits.argmax()

    # top-k keeps EXACTLY k candidates: what the compiled step hands the
    # categorical draw has finite support on the k largest logits and
    # nowhere else (nothing outside leaks in, nothing inside is filtered
    # out), and every draw lands in that set.  Which of the k a seed
    # draws is chance: 64 draws need not visit them all
    k = 3
    draw = jax.random.categorical

    def kept_and_drawn(l, key):
        given = []

        def spy(key, filtered, axis=-1):
            given.append(filtered)
            return draw(key, filtered, axis=axis)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax.random, "categorical", spy)
            tok = sample_logits(l, key, 1.0, top_k=k)
        return tok, given[0] > jnp.finfo(jnp.float32).min

    topk = jax.jit(lambda l, key: kept_and_drawn(l, key))
    allowed = set(np.argsort(logits[0])[-k:].tolist())
    for s in range(64):
        tok, kept = topk(jnp.asarray(logits), jax.random.PRNGKey(s))
        assert int(tok[0]) in allowed, (int(tok[0]), allowed)
    assert set(np.flatnonzero(np.asarray(kept)[0]).tolist()) == allowed

    # top-p never drops the argmax: even a top_p smaller than the
    # argmax's own probability keeps it (the smallest covering set)
    for p in (1e-6, 0.05, 0.3, 0.9):
        topp = jax.jit(lambda l, key, _p=p: sample_logits(l, key, 1.0,
                                                          top_p=_p))
        probs = np.exp(logits[0] - logits[0].max())
        probs /= probs.sum()
        order = np.argsort(-probs)
        cum = np.cumsum(probs[order])
        nucleus = set(order[:int(np.searchsorted(cum, p) + 1)].tolist())
        for s in range(16):
            tok = int(topp(jnp.asarray(logits), jax.random.PRNGKey(s))[0])
            assert tok in nucleus, (p, tok, nucleus)
        assert int(logits.argmax()) in nucleus


def _branch_free_sampler(logits, temperature, top_k, top_p, seed, step):
    """``sample_logits_data`` as it stood before the draw went behind a
    ``cond``: the whole draw for every row of every step.  Kept here as
    the yardstick the conditional form must match id for id."""
    import jax
    import jax.numpy as jnp

    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    temp = jnp.asarray(temperature, jnp.float32)
    tk = jnp.asarray(top_k, jnp.int32)
    tp = jnp.asarray(top_p, jnp.float32)
    neg = jnp.float32(jnp.finfo(jnp.float32).min)
    safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
    scaled = lf / safe_t[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    kk = jnp.clip(tk, 1, v)
    kth = jnp.take_along_axis(sorted_desc, (kk - 1)[:, None], axis=-1)
    apply_k = ((tk > 0) & (tk < v))[:, None]
    keep = jnp.where(apply_k, scaled >= kth, True)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut = (cum - probs) >= tp[:, None]
    kept_min = jnp.min(jnp.where(cut, jnp.inf, sorted_desc), axis=-1,
                       keepdims=True)
    keep = keep & (scaled >= kept_min)
    masked = jnp.where(keep, scaled, neg)
    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t))(
            jnp.asarray(seed, jnp.uint32), jnp.asarray(step, jnp.uint32))
    drawn = jax.vmap(jax.random.categorical)(keys, masked)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temp == 0, greedy, drawn).astype(jnp.int32)


def _sampler_inputs(batch, rows, truncate, dtype, vocab=384):
    import jax.numpy as jnp

    rng = np.random.RandomState(batch * 7 + len(rows))
    logits = jnp.asarray(rng.randn(batch, vocab) * 3.0, dtype)
    draws = {"greedy": np.zeros(batch, bool),
             "drawing": np.ones(batch, bool),
             "mixed": np.arange(batch) % 3 == 1}[rows]
    temp = np.where(draws, rng.uniform(0.5, 1.3, batch), 0.0)
    top_k = rng.randint(2, 40, batch) if truncate else np.zeros(batch)
    top_p = rng.uniform(0.5, 0.95, batch) if truncate else np.ones(batch)
    return (logits, temp.astype(np.float32), top_k.astype(np.int32),
            top_p.astype(np.float32),
            rng.randint(0, 2 ** 31, batch).astype(np.uint32),
            rng.randint(0, 50, batch).astype(np.uint32))


@pytest.mark.parametrize("truncate,dtype", [
    (False, "float32"), (True, "float32"),
    (False, "bfloat16"), (True, "bfloat16")])
@pytest.mark.parametrize("batch,rows", [
    (1, "greedy"), (1, "drawing"),
    (16, "greedy"), (16, "drawing"), (16, "mixed")])
def test_sampler_behind_the_cond_gives_the_branch_free_ids(
        batch, rows, truncate, dtype):
    import jax

    args = _sampler_inputs(batch, rows, truncate, dtype)
    want = np.asarray(jax.jit(_branch_free_sampler)(*args))
    got = jax.jit(sample_logits_data)(*args)
    assert got.dtype == np.int32 and got.shape == (batch,)
    np.testing.assert_array_equal(np.asarray(got), want)
    # and outside a jit, where the cond picks its branch eagerly
    np.testing.assert_array_equal(np.asarray(sample_logits_data(*args)),
                                  want)
    greedy_rows = args[1] == 0
    np.testing.assert_array_equal(
        want[greedy_rows], np.asarray(args[0]).argmax(-1)[greedy_rows])
    if rows != "greedy" and batch > 1:
        # the draw is a draw: of several rows some leave their argmax
        assert (want != np.asarray(args[0]).argmax(-1)).any()


def _primitives(jaxpr):
    """Every primitive's name in ``jaxpr`` and whatever it nests."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in _subjaxprs(eqn):
            names |= _primitives(sub)
    return names


def _subjaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sampler_keeps_its_sort_inside_one_cond(dtype):
    """A greedy step pays for no sort: at the top level of the jitted
    sampler there is the argmax, the predicate and ONE ``cond``; the
    sort, the cumulative sum and the random bits live in its drawing
    branch alone, and the other branch computes nothing."""
    import jax

    args = _sampler_inputs(16, "mixed", True, dtype)
    outer = jax.make_jaxpr(jax.jit(sample_logits_data))(*args).jaxpr
    (call,) = outer.eqns
    top = next(_subjaxprs(call))
    names = [e.primitive.name for e in top.eqns]
    heavy = {"sort", "cumsum", "random_bits"}
    assert not heavy & set(names), names
    assert names.count("cond") == 1 and "argmax" in names
    cond = top.eqns[names.index("cond")]
    inside = [_primitives(b.jaxpr) for b in cond.params["branches"]]
    drawing = [b for b in inside if "sort" in b]
    assert len(drawing) == 1 and heavy <= drawing[0], inside
    (other,) = [b for b in inside if "sort" not in b]
    assert not other & (heavy | {"argmax", "exp", "div"}), other
    # the cast to float32 moved into the draw with the rest: bfloat16
    # logits meet the argmax as given
    assert top.eqns[names.index("argmax")].invars[0].aval.dtype == dtype


def test_bucket_error_names_available_buckets():
    # the fix must be actionable from the exception alone: the message
    # names the configured buckets, not just the largest one
    m = _tiny_model()
    sess = DecodeSession(m, max_len=64, buckets=[8, 16])
    with pytest.raises(InvalidArgumentError,
                       match=r"available buckets: \[8, 16\]"):
        sess.generate(np.zeros((1, 20), np.int32), 4)
    pool = GenerationPool(m, max_len=64, slots=1, buckets=[8, 16])
    with pytest.raises(InvalidArgumentError,
                       match=r"available buckets: \[8, 16\]"):
        pool.submit(np.zeros(20, np.int32), 4)


def test_eos_early_stop_pads():
    m = _tiny_model()
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 128, (1, 5)).astype("int32")
    sess = DecodeSession(m, max_len=64, buckets=[8])
    ref = sess.generate(ids, 8)
    eos = int(ref[0, 2])  # force a hit at step 3
    got = sess.generate(ids, 8, eos_id=eos)
    assert got.shape == (1, 8)
    np.testing.assert_array_equal(got[0, :3], ref[0, :3])
    assert (got[0, 3:] == eos).all()  # padded, not hallucinated


def test_eos_per_row_masking_in_batch():
    # a row that hits EOS while its batch peers continue must emit
    # eos_id padding from then on, not the model's continuation
    m = _tiny_model()
    sess = DecodeSession(m, max_len=64, buckets=[8])
    rng = np.random.RandomState(9)
    ids = rng.randint(0, 128, (2, 5)).astype("int32")
    ref = sess.generate(ids, 8)
    eos = int(ref[0, 1])  # row 0 hits it at step 2; row 1 may not
    got = sess.generate(ids, 8, eos_id=eos)
    row0 = got[0]
    hit = int(np.argmax(row0 == eos))
    assert (row0[hit:] == eos).all(), row0
    # unfinished rows are unaffected by a peer's EOS
    row1_ref = ref[1]
    n_live = int(np.argmax(got[1] == eos)) if (got[1] == eos).any() \
        else got.shape[1]
    np.testing.assert_array_equal(got[1, :n_live], row1_ref[:n_live])


def test_sampling_config_validated():
    m = _tiny_model()
    with pytest.raises(InvalidArgumentError, match="top_p"):
        DecodeSession(m, max_len=32, buckets=[8], temperature=1.0,
                      top_p=0.0)
    with pytest.raises(InvalidArgumentError, match="temperature"):
        DecodeSession(m, max_len=32, buckets=[8], temperature=-0.5)
    with pytest.raises(InvalidArgumentError):
        sample_logits(np.zeros((1, 4), np.float32), None, 1.0, top_p=1.5)


def test_capacity_and_bucket_errors():
    m = _tiny_model()
    sess = DecodeSession(m, max_len=32, buckets=[16])
    ids = np.zeros((1, 20), np.int32)
    with pytest.raises(InvalidArgumentError, match="bucket"):
        sess.generate(ids, 4)  # 20 > largest bucket 16
    with pytest.raises(InvalidArgumentError, match="max_len"):
        sess.generate(np.zeros((1, 10), np.int32), 30)  # 10+30 > 32
    with pytest.raises(InvalidArgumentError, match="max_new_tokens"):
        sess.generate(np.zeros((1, 4), np.int32), 0)


def test_session_leaves_training_mode_alone():
    # a training loop may own a session for periodic sampling: neither
    # construction nor generation may flip the shared model to eval
    # (decode itself always traces in inference mode)
    m = _tiny_model()
    m.train()
    sess = DecodeSession(m, max_len=32, buckets=[8])
    sess.generate(np.zeros((1, 4), np.int32), 2)
    assert m.training
    assert all(l.training for l in m.sublayers(include_self=True))


def test_decode_cache_rejects_additive_mask():
    # a user mask is chunk-keyed while cached scores span max_len: the
    # combination cannot broadcast correctly, so it must fail loudly
    m = _tiny_model()
    cache = m.gen_decode_cache(1, 16)
    ids = np.zeros((1, 4), np.int32)
    mask = pt.to_tensor(np.zeros((4, 4), np.float32))
    with pytest.raises(InvalidArgumentError, match="attn_mask"):
        m(pt.to_tensor(ids), mask, cache=cache)


def test_per_slot_cache_chunk_write_matches_sequential():
    # the speculative verify path: a per-slot cache accepts an L-token
    # chunk whose logits (and cache writes) must equal feeding the same
    # tokens one step at a time — the multi-token append is a cost
    # change, never a math change
    m = _tiny_model()
    m.eval()
    rng = np.random.RandomState(11)
    ids = rng.randint(0, 128, (2, 4)).astype("int32")
    chunk_cache = m.gen_decode_cache(2, 16, per_slot=True)
    chunk_logits, chunk_cache = m(pt.to_tensor(ids), cache=chunk_cache)
    step_cache = m.gen_decode_cache(2, 16, per_slot=True)
    parts = []
    for t in range(4):
        lg, step_cache = m(pt.to_tensor(ids[:, t:t + 1]),
                           cache=step_cache)
        parts.append(np.asarray(lg.value))
    np.testing.assert_allclose(np.asarray(chunk_logits.value),
                               np.concatenate(parts, axis=1),
                               atol=2e-4, rtol=2e-3)
    for c_chunk, c_step in zip(chunk_cache, step_cache):
        np.testing.assert_array_equal(np.asarray(c_chunk.index),
                                      np.asarray(c_step.index))
        np.testing.assert_allclose(np.asarray(c_chunk.k),
                                   np.asarray(c_step.k), atol=1e-5)
        np.testing.assert_allclose(np.asarray(c_chunk.v),
                                   np.asarray(c_step.v), atol=1e-5)


def test_non_causal_model_rejected():
    # a bidirectional encoder through the cached path would get CAUSAL
    # masking — silently different logits; must refuse instead
    pt.seed(0)
    m = TransformerLM(vocab_size=64, hidden_size=32, num_layers=1,
                      num_heads=2, intermediate_size=64, max_position=64,
                      causal=False, dropout=0.0)
    with pytest.raises(InvalidArgumentError, match="causal"):
        m.gen_decode_cache(1, 16)
    with pytest.raises(InvalidArgumentError, match="causal"):
        DecodeSession(m, max_len=16, buckets=[8])


def test_max_len_validated_against_position_table():
    m = _tiny_model(max_position=64)
    with pytest.raises(InvalidArgumentError, match="position-embedding"):
        DecodeSession(m, max_len=128, buckets=[16])


def test_decode_attention_gate_conditions(monkeypatch):
    import jax
    import jax.numpy as jnp

    # the module is shadowed by the function in paddle_tpu.ops's
    # namespace; import the module object itself
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")

    # the gate memoizes the backend lookup (it runs on every trace);
    # clear the memo around the monkeypatch so the fake backend is seen
    # and cannot leak into later tests
    fa.reset_backend_memo()
    try:
        # CPU backend: never supported (the fused composition wins)
        assert not fa.decode_attention_supported((1, 8, 1, 64), 32768,
                                                 jnp.float32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fa.reset_backend_memo()
        ok = (1, 8, 1, 64)
        assert fa.decode_attention_supported(ok,
                                             fa.DECODE_FLASH_MIN_CACHE,
                                             jnp.bfloat16)
        # below the measured-crossover cache length: composition wins
        assert not fa.decode_attention_supported(
            ok, fa.DECODE_FLASH_MIN_CACHE - 1, jnp.bfloat16)
        # long query chunks belong to the prefill kernel path
        assert not fa.decode_attention_supported((1, 8, 9, 64), 32768,
                                                 jnp.bfloat16)
        # MXU-hostile head_dim
        assert not fa.decode_attention_supported((1, 8, 1, 48), 32768,
                                                 jnp.bfloat16)
    finally:
        fa.reset_backend_memo()


def test_default_buckets_cover_max_len():
    assert default_buckets(640) == [64, 128, 256, 512, 640]
    assert default_buckets(64) == [64]


def test_generation_pool_mixed_lengths_slot_refill():
    # 3 mixed-length requests through 2 slots: the third request enters
    # only when a slot frees (continuous batching), and every request's
    # tokens must equal its standalone batch-1 greedy generation
    m = _tiny_model()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 11, 7)]
    pool = create_generation_pool(m, max_len=64, slots=2, buckets=[16, 32])
    assert isinstance(pool, GenerationPool)
    outs = pool.generate(prompts, 6)
    sess = DecodeSession(m, max_len=64, buckets=[16, 32])
    for p, got in zip(prompts, outs):
        want = sess.generate(p[None], 6)[0]
        np.testing.assert_array_equal(got, want)
    # slot-batched machinery compiled once per function
    counts = pool.compile_counts()
    assert counts["pool_decode"] == 1 and counts["slot_insert"] == 1


def test_generation_pool_eos_and_queue_order():
    m = _tiny_model()
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 128, (4,)).astype("int32") for _ in range(3)]
    sess = DecodeSession(m, max_len=64, buckets=[8])
    eos = int(sess.generate(prompts[0][None], 6)[0, 1])
    pool = GenerationPool(m, max_len=64, slots=2, buckets=[8], eos_id=eos)
    rids = [pool.submit(p, 6) for p in prompts]
    results = pool.run()
    assert set(results) == set(rids)
    ref0 = sess.generate(prompts[0][None], 6)[0]
    got0 = results[rids[0]]
    # stops AT the eos token instead of generating past it
    assert got0[-1] == eos and len(got0) <= 6
    np.testing.assert_array_equal(got0, ref0[:len(got0)])


def test_empty_prompt_rejected():
    m = _tiny_model()
    sess = DecodeSession(m, max_len=32, buckets=[8])
    with pytest.raises(InvalidArgumentError, match="at least one token"):
        sess.generate(np.zeros((1, 0), np.int32), 3)
    pool = GenerationPool(m, max_len=32, slots=1, buckets=[8])
    with pytest.raises(InvalidArgumentError, match="at least one token"):
        pool.submit(np.zeros(0, np.int32), 3)


def test_pool_rejects_over_bucket_prompt_at_submit():
    # must fail at submit, not mid-refill (which would leak the slot)
    m = _tiny_model()
    pool = GenerationPool(m, max_len=64, slots=2, buckets=[16])
    with pytest.raises(InvalidArgumentError, match="bucket"):
        pool.submit(np.zeros(30, np.int32), 4)
    # the pool still serves normally afterwards
    out = pool.generate([np.zeros(5, np.int32)], 3)
    assert out[0].shape == (3,)


def test_pool_request_id_collision_rejected():
    m = _tiny_model()
    pool = GenerationPool(m, max_len=32, slots=1, buckets=[8])
    pool.submit(np.zeros(4, np.int32), 2, request_id=1)
    with pytest.raises(InvalidArgumentError, match="request_id"):
        pool.submit(np.zeros(4, np.int32), 2, request_id=1)
    auto = pool.submit(np.zeros(4, np.int32), 2)  # must skip the taken 1
    assert auto != 1
    results = pool.run()
    assert set(results) == {1, auto}


def test_decode_5x_faster_per_token_than_full_forward():
    """Acceptance: at prefill 512 on CPU, the cached decode step must be
    >= 5x faster than emitting one token via a full jitted re-forward.
    The FLOP gap is ~500x (one position vs 512), so 5x holds with wide
    margin over dispatch overhead and CI noise."""
    import time

    import jax

    m = _tiny_model(vocab=1024, hidden=128, heads=2)
    sess = DecodeSession(m, max_len=512 + 32, buckets=[512])
    rng = np.random.RandomState(7)
    ids = rng.randint(0, 1024, (1, 512)).astype("int32")

    # baseline: jitted full forward at the SAME length (conservative —
    # the honest uncached loop grows past 512 and recompiles per length)
    from paddle_tpu.jit import to_static
    fwd = to_static(m.forward)
    x = pt.to_tensor(ids)
    np.asarray(fwd(x).value)  # compile + warm

    def med(fn, n=5):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    t_full = med(lambda: np.asarray(fwd(x).value))

    cache, tok, key = sess.prefill(ids)
    params, bufs = sess._state_vals()
    state = {"c": cache, "t": tok, "k": key}

    def step():
        state["c"], state["t"], state["k"] = sess._decode_jit(
            params, bufs, state["c"], state["t"], state["k"])
        np.asarray(state["t"])  # host sync, like the generate loop

    step()  # warm (already compiled by prefill? no — compile decode here)
    t_tok = med(step)
    ratio = t_full / t_tok
    assert ratio >= 5.0, (t_full, t_tok, ratio)
