"""Quantized int8 KV cache — per-head-scaled storage, in-kernel dequant.

Pins the contracts the int8 cache lives on (docs/DESIGN.md §5d):

- quantize-on-write round-trips within one quantization step
  (``ops.quantize_kv`` / ``dequantize_kv``), and the int8-aware
  attention compositions (dense and paged) equal the explicit
  dequantize-then-attend reference exactly — the dtype changes BYTES
  STREAMED, never the math graph;
- greedy int8 generation emits fp32's tokens over the short-horizon
  corpus until a step whose fp32 top-2 margin is under the bounded
  quantization error, for dense AND paged layouts, session and pool
  (the acceptance contract), and cached int8 logits diverge from the
  fp32 full forward by no more than that bound;
- ``DecodeSession(cache_dtype="int8")`` still compiles exactly two
  functions — the scales are just more donated carry leaves;
- a freed paged slot's writes (values AND scales) are masked to the
  scratch block, so a reallocated block can never be read under a stale
  request's scales (cross-request scale leakage);
- unsupported cache dtypes fail at construction with a typed error
  naming the supported set, not as a shape/astype failure in the first
  compiled step;
- byte accounting is honest: int8 reachable bytes count the int8 K/V
  PLUS the riding fp32 scales and come in at <= 0.55x fp32 at every
  occupancy (the bench acceptance bound).
"""
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool, kv_reachable_bytes
from paddle_tpu.jit import DecodeSession
from paddle_tpu.models import TransformerLM


def _tiny_model(vocab=128, hidden=64, heads=4, layers=2, max_position=1024):
    pt.seed(0)
    return TransformerLM(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=2 * hidden,
        max_position=max_position, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.fixture(scope="module")
def sess_fp32(model):
    return DecodeSession(model, max_len=64, buckets=[16])


@pytest.fixture(scope="module")
def sess_int8(model):
    return DecodeSession(model, max_len=64, buckets=[16],
                         cache_dtype="int8")


# -- op level ------------------------------------------------------------

def test_quantize_kv_roundtrip_and_scale_shape():
    import jax.numpy as jnp

    from paddle_tpu.ops import dequantize_kv, quantize_kv

    rng = np.random.RandomState(0)
    x = (rng.randn(2, 4, 8, 16) * 3.0).astype(np.float32)
    q, s = quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.shape == x.shape[:-1]  # one scale per head per position
    back = np.asarray(dequantize_kv(q, s))
    # symmetric absmax int8: error is at most half a quantization step
    step = np.abs(x).max(axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(back - x) <= 0.5 * step + 1e-7)
    # an all-zero head row must quantize to zeros, not NaN (eps floor)
    qz, sz = quantize_kv(jnp.zeros((1, 2, 3, 4)))
    assert np.all(np.asarray(qz) == 0)
    assert np.all(np.isfinite(np.asarray(sz)))
    assert np.all(np.asarray(dequantize_kv(qz, sz)) == 0)


def test_int8_decode_attention_equals_explicit_dequant():
    # the in-composition dequant is EXACTLY dequantize-then-attend: the
    # int8 path changes where the up-cast happens, never the math
    import jax.numpy as jnp

    from paddle_tpu.ops import (decode_attention, dequantize_kv,
                                quantize_kv)

    rng = np.random.RandomState(1)
    q = rng.randn(2, 4, 1, 16).astype(np.float32)
    k = rng.randn(2, 4, 24, 16).astype(np.float32)
    v = rng.randn(2, 4, 24, 16).astype(np.float32)
    kq, ks = quantize_kv(jnp.asarray(k))
    vq, vs = quantize_kv(jnp.asarray(v))
    got = np.asarray(decode_attention(jnp.asarray(q), kq, vq,
                                      k_scale=ks, v_scale=vs))
    want = np.asarray(decode_attention(
        jnp.asarray(q), dequantize_kv(kq, ks), dequantize_kv(vq, vs)))
    np.testing.assert_array_equal(got, want)
    # and the quantized result tracks full precision within quant error
    ref = np.asarray(decode_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v)))
    np.testing.assert_allclose(got, ref, atol=0.05)


def test_int8_paged_decode_attention_matches_dense_int8():
    # paged int8: scales gather through the SAME table as their blocks,
    # so the gathered view equals the dense int8 composition exactly
    import jax.numpy as jnp

    from paddle_tpu.ops import (decode_attention, paged_decode_attention,
                                quantize_kv)

    rng = np.random.RandomState(2)
    b, h, bs, d, mb = 3, 2, 8, 16, 4
    nb = 1 + b * mb
    s = mb * bs
    k_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    v_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    kq, ks = quantize_kv(jnp.asarray(k_pool))
    vq, vs = quantize_kv(jnp.asarray(v_pool))
    table = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    lengths = np.array([5, 17, 32], np.int32)
    q = rng.randn(b, h, 1, d).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), kq, vq, jnp.asarray(table),
        lengths=jnp.asarray(lengths), k_scale=ks, v_scale=vs))
    kd = np.asarray(kq)[table].transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    vd = np.asarray(vq)[table].transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    ksd = np.asarray(ks)[table].transpose(0, 2, 1, 3).reshape(b, h, s)
    vsd = np.asarray(vs)[table].transpose(0, 2, 1, 3).reshape(b, h, s)
    neg = np.finfo(np.float32).min
    bias = np.where(np.arange(s)[None, :] < lengths[:, None], 0.0,
                    neg)[:, None, None, :].astype(np.float32)
    want = np.asarray(decode_attention(
        jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd),
        bias=jnp.asarray(bias), k_scale=jnp.asarray(ksd),
        v_scale=jnp.asarray(vsd)))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # poisoned scratch-block scales must not leak through the mask
    ks_poison = np.asarray(ks).copy()
    ks_poison[0] = 1e9
    got2 = np.asarray(paged_decode_attention(
        jnp.asarray(q), kq, vq, jnp.asarray(table),
        lengths=jnp.asarray(lengths), k_scale=jnp.asarray(ks_poison),
        v_scale=vs))
    np.testing.assert_allclose(got2, want, atol=1e-6)


# -- greedy agreement (the acceptance contract) --------------------------

# What an int8 cache can promise about greedy tokens is a MARGIN
# property.  Quantization moves a cached logit by at most
# ``_INT8_LOGIT_TOL`` on this model (the bound
# ``test_int8_logit_divergence_bounded`` holds it to; about 0.02 is
# observed), so the int8 run emits the fp32 run's tokens until a step
# whose fp32 top-2 margin is under that tolerance: such a step is a
# near-tie no cache dtype can promise (a random-init toy model has them;
# a trained model's margins are orders of magnitude wider).  Where the
# two runs part, they part there, and on a token whose fp32 logit is
# within the tolerance of the top.
_INT8_LOGIT_TOL = 0.08


def _fp32_greedy_with_logits(model, sess_fp32, ids, gen):
    """(fp32 greedy tokens, the logits each greedy step saw) — the
    logits read from ONE uncached full forward over the generated
    sequence (causality makes its per-position logits the ones each
    greedy step saw)."""
    got = sess_fp32.generate(ids, gen)
    full_seq = np.concatenate([np.asarray(ids), got], axis=1)
    logits = np.asarray(model(pt.to_tensor(full_seq)).value)
    return got, logits[:, ids.shape[1] - 1:-1]  # the emitting positions


@pytest.mark.parametrize("layout_kw", [
    pytest.param({}, id="dense"),
    pytest.param(dict(cache_layout="paged", block_size=8), id="paged"),
])
def test_int8_greedy_token_identical_short_horizon(model, sess_fp32,
                                                   layout_kw):
    sess8 = DecodeSession(model, max_len=64, buckets=[16],
                          cache_dtype="int8", **layout_kw)
    model.eval()
    same = total = 0
    for seed in range(8):
        rng = np.random.RandomState(seed)
        length = int(rng.randint(3, 15))
        ids = rng.randint(0, 128, (2, length)).astype("int32")
        want, logits = _fp32_greedy_with_logits(model, sess_fp32, ids, 8)
        got = sess8.generate(ids, 8)
        top2 = np.sort(logits, axis=-1)[..., -2:]
        margins = top2[..., 1] - top2[..., 0]
        for row in range(len(ids)):
            what = "seed %d row %d" % (seed, row)
            n = chip_smoke.check_same_until_near_tie(
                got[row], want[row], margins[row], _INT8_LOGIT_TOL, what)
            if n < len(want[row]):
                step = logits[row, n]
                gap = step[want[row, n]] - step[got[row, n]]
                assert gap < _INT8_LOGIT_TOL, (what, n, gap)
            same += n
            total += len(want[row])
    # near-ties are the exception: the check has teeth only if most of
    # the corpus is compared token for token
    assert same >= 0.9 * total, "only %d of %d tokens compared" % (
        same, total)


def test_int8_logit_divergence_bounded(model):
    """Property: cached int8 logits track the fp32 full forward within a
    bounded quantization error — measured headroom is ~4x (max observed
    divergence 0.021 on logits of magnitude ~3), so a regression in the
    write path (wrong scale, wrong position) trips this long before it
    could flip a greedy argmax."""
    model.eval()
    rng = np.random.RandomState(3)
    for _ in range(3):
        ids = rng.randint(0, 128, (2, 12)).astype("int32")
        full = np.asarray(model(pt.to_tensor(ids)).value)
        cache = model.gen_decode_cache(2, 32, dtype="int8")
        logits, cache = model(pt.to_tensor(ids[:, :8]), cache=cache)
        parts = [np.asarray(logits.value)]
        for t in range(8, 12):
            lg, cache = model(pt.to_tensor(ids[:, t:t + 1]), cache=cache)
            parts.append(np.asarray(lg.value))
        got = np.concatenate(parts, axis=1)
        err = float(np.abs(got - full).max())
        assert err < _INT8_LOGIT_TOL, err
        assert err > 0.0  # int8 is genuinely lossy: exact == not-int8


def test_int8_exactly_two_compiles(model):
    # the scales are extra donated carry leaves in the SAME pytree: the
    # exactly-two-compiles contract survives quantization verbatim
    for kw in ({}, dict(cache_layout="paged", block_size=8)):
        sess = DecodeSession(model, max_len=64, buckets=[16],
                             cache_dtype="int8", **kw)
        rng = np.random.RandomState(5)
        for length in (4, 9, 16):
            sess.generate(rng.randint(0, 128, (1, length)).astype("int32"),
                          4)
        assert sess.compile_counts() == {"prefill": 1, "decode": 1}, kw


# -- pool / slot-batched layout ------------------------------------------

def test_pool_int8_matches_session_dense_and_paged(model, sess_int8):
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 11, 7)]
    for kw in ({}, dict(cache_layout="paged", block_size=8)):
        pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                              cache_dtype="int8", **kw)
        outs = pool.generate(prompts, 6)
        for p, got in zip(prompts, outs):
            np.testing.assert_array_equal(
                got, sess_int8.generate(p[None], 6)[0], err_msg=str(kw))


def test_paged_freed_block_scales_masked_to_scratch(model, sess_int8):
    """The slot-churn scale-leakage hazard: a released slot keeps
    decoding through the batched step (inactive rows still compute),
    and without table masking its writes — int8 values AND scales —
    would land in blocks the allocator may already have handed to
    another request.  Pin that freed blocks stay byte-identical while
    the masked writes land in the scratch block, and that a request
    decoding through the REUSED blocks is token-correct."""
    rng = np.random.RandomState(4)
    a = rng.randint(0, 128, (9,)).astype("int32")
    b = rng.randint(0, 128, (13,)).astype("int32")
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                          cache_layout="paged", block_size=8,
                          cache_dtype="int8")
    ra = pool.submit(a, 20)
    rb = pool.submit(b, 20)
    pool.step()
    pool.step()
    slot_b = [s for s, st in pool._active.items() if st.rid == rb][0]
    freed = list(pool._slot_blocks[slot_b])
    pool.release(slot_b)
    first = pool._cache[0]
    scales_before = np.asarray(first.k_scale)[freed].copy()
    values_before = np.asarray(first.k)[freed].copy()
    scratch_before = np.asarray(first.k_scale)[0].copy()
    pool.step()
    pool.step()
    first = pool._cache[0]
    # no stale write reached the freed blocks: values or scales
    np.testing.assert_array_equal(np.asarray(first.k_scale)[freed],
                                  scales_before)
    np.testing.assert_array_equal(np.asarray(first.k)[freed],
                                  values_before)
    # the released slot's masked writes landed in the scratch block
    assert not np.array_equal(np.asarray(first.k_scale)[0],
                              scratch_before)
    results = pool.run()
    np.testing.assert_array_equal(results[ra],
                                  sess_int8.generate(a[None], 20)[0])
    # churn: a new request decodes through the freed-and-reused blocks
    rc = pool.submit(b, 6)
    np.testing.assert_array_equal(pool.run()[rc],
                                  sess_int8.generate(b[None], 6)[0])


# -- dtype validation ----------------------------------------------------

def test_unsupported_cache_dtype_typed_error(model):
    from paddle_tpu.nn.layer.transformer import SUPPORTED_CACHE_DTYPES

    # the error must name the supported set — actionable from the
    # exception alone, instead of a shape/astype failure in the trace
    with pytest.raises(InvalidArgumentError, match="int8"):
        model.gen_decode_cache(1, 32, dtype="int4")
    with pytest.raises(InvalidArgumentError, match="float32"):
        model.gen_decode_cache(1, 32, dtype="complex64")
    # DecodeSession fails at CONSTRUCTION, before any trace
    with pytest.raises(InvalidArgumentError, match="supported cache"):
        DecodeSession(model, max_len=32, buckets=[8], cache_dtype="uint8")
    with pytest.raises(InvalidArgumentError, match="supported cache"):
        GenerationPool(model, max_len=32, slots=1, buckets=[8],
                       cache_dtype="no-such-dtype")
    assert "int8" in SUPPORTED_CACHE_DTYPES


def test_int8_cache_allocation_shapes(model):
    cache = model.gen_decode_cache(2, 32, dtype="int8")
    assert str(cache[0].k.dtype) == "int8"
    assert cache[0].k_scale.shape == cache[0].k.shape[:-1]
    assert str(cache[0].k_scale.dtype) == "float32"
    # float caches carry NO scale leaves (the pytree — and so the
    # compiled steps — are unchanged from the pre-quantization layout)
    fp = model.gen_decode_cache(2, 32)
    assert fp[0].k_scale is None and fp[0].v_scale is None
    paged = model.gen_decode_cache(2, 32, dtype="int8", layout="paged",
                                   block_size=8)
    assert paged[0].k_scale.shape == paged[0].k.shape[:-1]


# -- byte accounting -----------------------------------------------------

_DIMS = dict(max_len=640, num_layers=4, num_heads=8, head_dim=64)


def test_kv_reachable_bytes_int8_counts_scales():
    fp = kv_reachable_bytes([640], layout="dense", **_DIMS)
    q8 = kv_reachable_bytes([640], layout="dense", dtype="int8", **_DIMS)
    # int8 K/V (1 byte/elem) + one fp32 scale per K and V head-position
    assert q8 == 640 * 2 * 4 * 8 * (64 + 4)
    assert q8 / fp == (64 + 4) / (4 * 64)


@pytest.mark.parametrize("tokens", [1, 17, 100, 320, 639, 640])
def test_kv_reachable_bytes_int8_is_about_half_at_every_occupancy(tokens):
    # both layouts, and a block size that does not divide max_len
    for layout, bs in (("dense", 32), ("paged", 32), ("paged", 24)):
        f = kv_reachable_bytes([tokens] * 4, layout=layout,
                               block_size=bs, **_DIMS)
        q = kv_reachable_bytes([tokens] * 4, layout=layout,
                               block_size=bs, dtype="int8", **_DIMS)
        assert q <= 0.55 * f, (layout, bs, q, f)


def test_cache_stats_reports_int8_dtype_and_bytes(model):
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                          cache_layout="paged", block_size=8,
                          cache_dtype="int8")
    pool.submit(np.zeros(9, np.int32), 4)
    pool.step()
    stats = pool.cache_stats()
    assert stats["cache_dtype"] == "int8"
    assert stats["reachable_bytes"] == kv_reachable_bytes(
        [9 + 4], max_len=64, num_layers=2, num_heads=4, head_dim=16,
        layout="paged", block_size=8, dtype="int8")
    fp_stats = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                              cache_layout="paged",
                              block_size=8).cache_stats()
    assert fp_stats["cache_dtype"] == "float32"
    assert stats["dense_equiv_bytes"] <= \
        0.55 * fp_stats["dense_equiv_bytes"]
    assert stats["pool_bytes"] <= 0.55 * fp_stats["pool_bytes"]
    pool.run()
