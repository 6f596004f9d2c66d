"""Fused pallas decode-attention kernel (docs/DESIGN.md §5l).

Pins the contracts the kernel route lives on, all on CPU via
``pallas_call(..., interpret=True)`` — the interpret-mode testing
contract: the SAME kernel body the TPU compiles is executed by the
pallas interpreter, so numeric identity against the XLA composition is
tier-1-testable without a chip, and only the measured crossover (which
route is FASTER) is left to on-chip sweeps:

- kernel-vs-composition numeric identity for paged AND dense caches,
  fp32 AND int8, query chunks Lq in {1, 4, 8} (decode + speculative
  verify shapes), scalar and per-row ``lengths``;
- masking: scratch-block garbage and stale table rows past the valid
  prefix never leak into the softmax;
- routing: ``route=`` forcing and the ambient ``decode_route`` context,
  typed errors on unknown routes, the backend-lookup memo + reset hook;
- the serving contract: a ``GenerationPool`` slot-churn run with
  ``route="pallas"`` emits BYTE-IDENTICAL greedy tokens to
  ``route="composition"`` with unchanged compile counts.
"""
import importlib

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool
from paddle_tpu.jit import DecodeSession
from paddle_tpu.models import TransformerLM

fa = importlib.import_module("paddle_tpu.ops.flash_attention")
pd = importlib.import_module("paddle_tpu.ops.pallas_decode")


def _paged_case(rng, b, h, bs, d, mb, lq, quant):
    import jax.numpy as jnp

    from paddle_tpu.ops import quantize_kv

    nb = 1 + b * mb
    q = jnp.asarray(rng.randn(b, h, lq, d).astype(np.float32))
    k_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    v_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    table = jnp.asarray(
        1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb))
    if quant:
        k_pool, ks = quantize_kv(k_pool)
        v_pool, vs = quantize_kv(v_pool)
    else:
        k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)
        ks = vs = None
    return q, k_pool, v_pool, table, ks, vs


@pytest.mark.parametrize("lq", [1, 4, 8])
@pytest.mark.parametrize("quant", [False, True],
                         ids=["fp32", "int8"])
def test_paged_kernel_matches_composition(lq, quant):
    # the core §5l identity: forced kernel == forced composition for
    # the paged cache, per-row lengths, to float-reduction noise
    rng = np.random.RandomState(0)
    b, h, bs, d, mb = 3, 2, 8, 16, 4
    q, k_pool, v_pool, table, ks, vs = _paged_case(rng, b, h, bs, d, mb,
                                                   lq, quant)
    import jax.numpy as jnp

    lengths = jnp.asarray(np.array([5, 17, 32], np.int32))
    got = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, lengths=lengths, k_scale=ks,
        v_scale=vs, route="pallas"))
    want = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, lengths=lengths, k_scale=ks,
        v_scale=vs, route="composition"))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_paged_kernel_scalar_lengths_and_qpos():
    # scalar lengths broadcast over rows; q_pos (the decode forwards'
    # index-form mask) combines with lengths by min — both paths agree
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    b, h, bs, d, mb, lq = 2, 2, 8, 16, 3, 4
    q, k_pool, v_pool, table, _, _ = _paged_case(rng, b, h, bs, d, mb,
                                                 lq, False)
    for kwargs in (dict(lengths=jnp.asarray(13, jnp.int32)),
                   dict(q_pos=jnp.asarray([3, 4, 5, 6], jnp.int32)),
                   dict(lengths=jnp.asarray([9, 21], jnp.int32),
                        q_pos=jnp.asarray(
                            rng.randint(0, mb * bs, (b, lq)),
                            jnp.int32))):
        got = np.asarray(fa.paged_decode_attention(
            q, k_pool, v_pool, table, route="pallas", **kwargs))
        want = np.asarray(fa.paged_decode_attention(
            q, k_pool, v_pool, table, route="composition", **kwargs))
        np.testing.assert_allclose(got, want, atol=2e-6,
                                   err_msg=str(sorted(kwargs)))


@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
def test_dense_kernel_matches_composition(quant):
    # the dense-cache variant on the same inner loop, over several
    # sequence tiles (S=640 -> five tiles of 128, the largest of the
    # kernel's bounded tile set that divides it)
    import jax.numpy as jnp

    from paddle_tpu.ops import quantize_kv

    rng = np.random.RandomState(2)
    b, h, s, d, lq = 2, 3, 640, 16, 4
    q = jnp.asarray(rng.randn(b, h, lq, d).astype(np.float32))
    k = rng.randn(b, h, s, d).astype(np.float32)
    v = rng.randn(b, h, s, d).astype(np.float32)
    if quant:
        k, ks = quantize_kv(k)
        v, vs = quantize_kv(v)
    else:
        k, v, ks, vs = jnp.asarray(k), jnp.asarray(v), None, None
    q_pos = jnp.asarray(rng.randint(0, s, (b, lq)), jnp.int32)
    got = np.asarray(fa.decode_attention(
        q, k, v, q_pos=q_pos, k_scale=ks, v_scale=vs, route="pallas"))
    want = np.asarray(fa.decode_attention(
        q, k, v, q_pos=q_pos, k_scale=ks, v_scale=vs,
        route="composition"))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_kernel_streams_additive_bias():
    # external callers' additive bias is streamed block-wise ([B,1,L,S]
    # here); an incompatible bias shape raises a typed error when the
    # kernel is FORCED (auto would quietly keep the composition)
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    b, h, s, d, lq = 2, 2, 32, 16, 2
    q = jnp.asarray(rng.randn(b, h, lq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    bias = np.where(rng.rand(b, 1, lq, s) < 0.25,
                    np.finfo(np.float32).min, 0.0).astype(np.float32)
    bias[..., 0] = 0.0  # every softmax keeps at least one key
    got = np.asarray(fa.decode_attention(q, k, v,
                                         bias=jnp.asarray(bias),
                                         route="pallas"))
    want = np.asarray(fa.decode_attention(q, k, v,
                                          bias=jnp.asarray(bias),
                                          route="composition"))
    np.testing.assert_allclose(got, want, atol=2e-6)
    with pytest.raises(InvalidArgumentError, match="bias"):
        fa.decode_attention(q, k, v, bias=jnp.zeros((lq, s)),
                            route="pallas")


def test_kernel_masks_scratch_and_stale_table():
    # the §5b slot-churn hazard, at the kernel layer: poison the scratch
    # block AND point the tail of the table at it (stale/unmapped rows),
    # with a ragged final block over-hanging `lengths` — no garbage may
    # reach the output
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    b, h, bs, d, mb, lq = 2, 2, 8, 16, 4, 1
    nb = 1 + b * mb
    q = jnp.asarray(rng.randn(b, h, lq, d).astype(np.float32))
    k_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    v_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    k_pool[0] = 1e9  # scratch-block poison
    v_pool[0] = 1e9
    table = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    table[:, 2:] = 0  # stale tail: unmapped rows point at scratch
    lengths = jnp.asarray(np.array([11, 16], np.int32))  # within 2 blks
    got = np.asarray(fa.paged_decode_attention(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        lengths=lengths, route="pallas"))
    want = np.asarray(fa.paged_decode_attention(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        lengths=lengths, route="composition"))
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.all(np.abs(got) < 1e6), "scratch poison leaked"


def test_route_validation_and_context():
    # typed errors on unknown routes at every entry (op kwarg, session
    # constructor, ambient context); the ambient context restores on exit
    with pytest.raises(InvalidArgumentError, match="route"):
        fa.normalize_decode_route("fused")
    with pytest.raises(InvalidArgumentError, match="route"):
        DecodeSession(_tiny_model(), max_len=32, buckets=[16],
                      route="kernel")
    assert fa._route_stack()[-1] == "auto"
    with fa.decode_route("pallas"):
        assert fa._route_stack()[-1] == "pallas"
        with fa.decode_route("composition"):
            assert fa._route_stack()[-1] == "composition"
        assert fa._route_stack()[-1] == "pallas"
    assert fa._route_stack()[-1] == "auto"


def test_route_context_is_thread_local():
    # the serving engine traces on its loop thread: another thread's
    # ambient route must never leak into (or be popped by) this one
    import threading

    seen = {}

    def worker():
        seen["start"] = fa._route_stack()[-1]
        with fa.decode_route("composition"):
            seen["inside"] = fa._route_stack()[-1]

    with fa.decode_route("pallas"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert fa._route_stack()[-1] == "pallas"
    assert seen == {"start": "auto", "inside": "composition"}


def test_backend_memo_and_reset_hook():
    # the per-trace jax.default_backend() lookup in the two decode
    # gates is memoized; reset_backend_memo is the test seam
    import jax

    fa.reset_backend_memo()
    assert fa._cached_backend() == jax.default_backend()
    # memo survives a monkeypatched backend until reset
    real = fa._cached_backend()
    orig = jax.default_backend
    try:
        jax.default_backend = lambda: "tpu"
        assert fa._cached_backend() == real  # memoized: no re-lookup
        fa.reset_backend_memo()
        assert fa._cached_backend() == "tpu"
    finally:
        jax.default_backend = orig
        fa.reset_backend_memo()


def test_forced_pallas_keeps_composition_for_long_chunks():
    # route="pallas" forces the kernel only where it structurally
    # applies (Lq <= MAX_KERNEL_QUERY_CHUNK); a prefill-shaped chunk
    # quietly keeps the composition — which is how a forced session
    # still prefills (its bucket chunk is long) yet decodes fused
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    b, h, s, d = 1, 2, 32, 16
    lq = pd.MAX_KERNEL_QUERY_CHUNK + 1
    q = jnp.asarray(rng.randn(b, h, lq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    got = np.asarray(fa.decode_attention(q, k, v, route="pallas"))
    want = np.asarray(fa.decode_attention(q, k, v, route="composition"))
    np.testing.assert_array_equal(got, want)  # same path, same bytes


def test_forced_pallas_refuses_by_name(monkeypatch):
    # a decode-sized chunk the kernel cannot take is REFUSED under the
    # forced route, naming the reason — it never decodes on the
    # composition behind the caller's back; "auto" just keeps the
    # composition.  The interpreter refuses only a cache length with no
    # bounded sequence tile; compiled mode adds Mosaic's layout rules.
    import jax
    import jax.numpy as jnp

    assert pd.dense_seq_block(1024) == 512 and pd.dense_seq_block(640) == 128
    assert pd.dense_seq_block(40) == 40        # short: one whole tile
    assert pd.dense_seq_block(520) is None     # long, nothing divides
    assert pd.dense_seq_block(36) is None      # short but not whole sublanes
    q = jnp.zeros((1, 2, 1, 16), jnp.float32)
    kv = jnp.zeros((1, 2, 520, 16), jnp.float32)
    with pytest.raises(InvalidArgumentError, match="no sequence tile"):
        fa.decode_attention(q, kv, kv, route="pallas")
    fa.decode_attention(q, kv, kv, route="auto")     # composition, quietly
    for args, match in (((128, 12, 48), "multiple of the 8"),
                        ((48, 32, 128), "head_dim 48"),
                        ((128, None, 520), "no sequence tile"),
                        ((128, 32, 128, True), "additive bias")):
        assert match in pd.mosaic_refusal(*args)
    for args in ((128, 32, 1024), (64, 8, 1024), (256, 128, 128, True),
                 (128, 64, 64, True)):
        assert pd.mosaic_refusal(*args) is None
    # compiled mode: the same call that the interpreter takes is refused
    pool = jnp.zeros((3, 2, 12, 16), jnp.float32)     # block of 12, D=16
    table = jnp.zeros((1, 2), jnp.int32)
    fa.paged_decode_attention(q, pool, pool, table, route="pallas")
    fa.reset_backend_memo()
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fa.reset_backend_memo()
        with pytest.raises(InvalidArgumentError, match="head_dim 16"):
            fa.paged_decode_attention(q, pool, pool, table,
                                      route="pallas")
        # the prefill-shaped chunk keeps the composition by design
        long_q = jnp.zeros((1, 2, pd.MAX_KERNEL_QUERY_CHUNK + 1, 16))
        fa.paged_decode_attention(long_q, pool, pool, table,
                                  route="pallas")
    finally:
        monkeypatch.undo()
        fa.reset_backend_memo()


def _tiny_model(vocab=128, hidden=64, heads=4, layers=2):
    pt.seed(0)
    return TransformerLM(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=2 * hidden,
        max_position=1024, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.mark.parametrize("layout,dtype", [("dense", "float32"),
                                          ("dense", "int8"),
                                          ("paged", "float32"),
                                          ("paged", "int8")])
def test_session_route_pallas_byte_identical(model, layout, dtype):
    # the acceptance contract: route="pallas" (interpret mode on CPU)
    # generates BYTE-IDENTICAL greedy tokens to route="composition"
    # across layouts x dtypes, with the exactly-two-compiles contract
    # intact on both sides
    rng = np.random.RandomState(8)
    ids = rng.randint(0, 128, (2, 12)).astype("int32")
    comp = DecodeSession(model, max_len=64, buckets=[16],
                         cache_layout=layout, block_size=8,
                         cache_dtype=dtype, route="composition")
    pal = DecodeSession(model, max_len=64, buckets=[16],
                        cache_layout=layout, block_size=8,
                        cache_dtype=dtype, route="pallas")
    np.testing.assert_array_equal(pal.generate(ids, 8),
                                  comp.generate(ids, 8))
    assert pal.compile_counts() == comp.compile_counts() \
        == {"prefill": 1, "decode": 1}


def test_pool_slot_churn_route_identity(model):
    # the serving-side acceptance case: paged pool under slot churn
    # (mid-decode submits, block reuse) — forced kernel tokens are
    # byte-identical to forced composition, compile counts unchanged,
    # and the route is stamped in cache_stats for the serving gauges
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 11, 7, 3, 14)]

    def churn(route):
        pool = GenerationPool(model, max_len=64, slots=2,
                              buckets=[16, 32], cache_layout="paged",
                              block_size=8, num_blocks=17, route=route)
        rids = [pool.submit(p, 6) for p in prompts[:2]]
        for _ in range(3):
            pool.step()
        rids += [pool.submit(p, 6) for p in prompts[2:]]
        res = pool.run()
        return ([res[r] for r in rids], pool.compile_counts(),
                pool.cache_stats()["decode_route"])

    toks_c, counts_c, route_c = churn("composition")
    toks_p, counts_p, route_p = churn("pallas")
    assert (route_c, route_p) == ("composition", "pallas")
    assert counts_p == counts_c
    for a, b in zip(toks_c, toks_p):
        np.testing.assert_array_equal(a, b)


def test_auto_route_on_cpu_is_composition(model):
    # "auto" off-TPU must be the composition bit-for-bit: the gates say
    # no kernel, so the traced program is the same program
    rng = np.random.RandomState(10)
    ids = rng.randint(0, 128, (1, 9)).astype("int32")
    auto = DecodeSession(model, max_len=48, buckets=[16], route="auto")
    comp = DecodeSession(model, max_len=48, buckets=[16],
                         route="composition")
    np.testing.assert_array_equal(auto.generate(ids, 6),
                                  comp.generate(ids, 6))
