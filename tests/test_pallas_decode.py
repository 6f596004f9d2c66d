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
- cost follows the live K/V: the head chunk is a function of shapes,
  blocks past a row's last visible one are never computed (NaN there
  stays there), a row that sees nothing emits zeros, and the step that
  masks an inactive slot's table masks its index and restores both;
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


def _kv_budget(monkeypatch, heads, bs, d, itemsize):
    """Hold the K/V blocks of a grid step to ``heads`` heads (the chunk
    comes from shapes and this one constant) and drop the traces made
    under another budget."""
    monkeypatch.setattr(pd, "_KV_VMEM_BUDGET",
                        heads * 2 * bs * d * (2 * itemsize + 4))
    pd._paged_call.clear_cache()
    pd._dense_call.clear_cache()


@pytest.mark.parametrize("lq", [1, 4, 8])
@pytest.mark.parametrize("form,quant", [
    ("plain", False), ("plain", True), ("bias", False), ("bias", True),
    ("grouped", False)],
    ids=["plain-fp32", "plain-int8", "bias-fp32", "bias-int8",
         "grouped-fp32"])
@pytest.mark.parametrize("chunk", ["one", "part", "all"])
def test_paged_kernel_head_chunks_match_composition(monkeypatch, chunk,
                                                    form, quant, lq):
    # every head chunk the rule can choose (a float pool of 4 heads: 1,
    # 2, 4; an int8 pool of 32: 8, 16, 32, its scale block's sublanes),
    # every row count, scales, bias and grouped rows: one answer
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    b, bs, d, mb = 3, 8, 16, 4
    h = 32 if quant else 4
    hc = {"one": h // 4, "part": h // 2, "all": h}[chunk]
    if quant:
        assert hc % 8 == 0
    _kv_budget(monkeypatch, hc, bs, d, 1 if quant else 4)
    assert pd.head_chunk(h, bs, d, 1 if quant else 4, quant) == hc
    hq = 2 * h if form == "grouped" else h
    q, k_pool, v_pool, table, ks, vs = _paged_case(rng, b, h, bs, d, mb,
                                                   lq, quant)
    q = jnp.asarray(rng.randn(b, hq, lq, d).astype(np.float32))
    q_pos = np.array([3, 17, 24], np.int32)[:, None] + (
        0 if form == "grouped" else np.arange(lq, dtype=np.int32))
    kwargs = dict(k_scale=ks, v_scale=vs,
                  q_pos=jnp.asarray(np.broadcast_to(q_pos, (b, lq))))
    if form == "bias":
        bias = np.where(rng.rand(b, h, lq, mb * bs) < 0.2,
                        np.finfo(np.float32).min, 0.0).astype(np.float32)
        bias[..., 0] = 0.0  # every softmax keeps at least one key
        kwargs["bias"] = jnp.asarray(bias)
    got = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, route="pallas", **kwargs))
    want = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, route="composition", **kwargs))
    np.testing.assert_allclose(got, want, atol=4e-6)
    pd._paged_call.clear_cache()


@pytest.mark.parametrize("h,bs,d,itemsize,quant,want", [
    (16, 32, 128, 4, False, 16),    # gpt-1p3b: 4 x 256 KB, every head
    (16, 32, 128, 1, True, 16),     # its int8 pool
    (4, 128, 128, 2, False, 4),     # sdar-30b-a3b: 4 x 128 KB
    (32, 128, 128, 4, False, 8),    # 4 x 2 MB a step would not fit: split
    (32, 128, 128, 1, True, 16),    # int8: a multiple of 8
    (12, 512, 256, 1, True, 12),    # int8, nothing legal fits: all heads
    (12, 512, 256, 4, False, 1),    # a dense tile of 512 x 256: one head
    (2, 8, 16, 4, False, 2),
], ids=["gpt", "gpt-int8", "sdar", "split", "split-int8", "int8-whole",
        "dense-tile", "toy"])
def test_head_chunk_comes_from_shapes(h, bs, d, itemsize, quant, want):
    got = pd.head_chunk(h, bs, d, itemsize, quant)
    assert got == want and h % got == 0
    if quant:
        assert got % 8 == 0 or got == h


@pytest.mark.parametrize("case", ["lq1", "lq4", "int8", "grouped", "bias",
                                  "dense"])
def test_dead_blocks_are_never_computed(case):
    # every block past a row's last visible one is NaN (K, V and
    # scales): were it fetched into the arithmetic, 0 x NaN would be in
    # the sums.  The output is finite and the clean run's, to the bit
    import jax.numpy as jnp

    rng = np.random.RandomState(12)
    b, h, bs, d, mb = 3, 2, 8, 16, 4
    lq = 1 if case in ("lq1", "int8") else 4
    quant = case == "int8"
    last = np.array([0, 2, 1])                  # last live block a row
    q_pos = last[:, None] * bs + np.array([[2], [7], [0]]) \
        - np.arange(lq)[::-1][None, :] * (case != "grouped")
    q_pos = np.maximum(q_pos, 0).astype(np.int32)
    hq = 2 * h if case == "grouped" else h
    q = jnp.asarray(rng.randn(b, hq, lq, d).astype(np.float32))
    kwargs = dict(q_pos=jnp.asarray(q_pos))
    if case == "bias":
        kwargs["bias"] = jnp.asarray(
            rng.randn(b, 1, lq, mb * bs).astype(np.float32))
    if case == "dense":
        s = 640                                 # five tiles of 128
        k = rng.randn(b, h, s, d).astype(np.float32)
        v = rng.randn(b, h, s, d).astype(np.float32)
        kwargs["q_pos"] = jnp.asarray(q_pos * 16)   # tiles 0, 2, 1
        dead = (np.arange(s)[None, :] // 128) > last[:, None]
        clean = fa.decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                    route="pallas", **kwargs)
        k[np.broadcast_to(dead[:, None, :], (b, h, s))] = np.nan
        v[np.broadcast_to(dead[:, None, :], (b, h, s))] = np.nan
        got = fa.decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                  route="pallas", **kwargs)
    else:
        _, k_pool, v_pool, table, ks, vs = _paged_case(rng, b, h, bs, d,
                                                       mb, lq, quant)
        kwargs.update(k_scale=ks, v_scale=vs)
        clean = fa.paged_decode_attention(q, k_pool, v_pool, table,
                                          route="pallas", **kwargs)
        dead = np.asarray(table)[np.arange(mb)[None, :] > last[:, None]]
        if quant:
            # an int8 pool holds no NaN: its scales do
            kwargs.update(k_scale=ks.at[dead].set(jnp.nan),
                          v_scale=vs.at[dead].set(jnp.nan))
        else:
            k_pool = k_pool.at[dead].set(jnp.nan)
            v_pool = v_pool.at[dead].set(jnp.nan)
        got = fa.paged_decode_attention(q, k_pool, v_pool, table,
                                        route="pallas", **kwargs)
    got, clean = np.asarray(got), np.asarray(clean)
    assert np.isfinite(got).all(), "a dead block reached the arithmetic"
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("lq", [1, 5])
def test_row_that_sees_nothing_emits_zeros(lq):
    # q_pos < 0: no key is visible.  The row costs one block (wholly
    # masked) and emits 0, never NaN; the rows beside it are exact
    import jax.numpy as jnp

    rng = np.random.RandomState(13)
    b, h, bs, d, mb = 3, 2, 8, 16, 4
    q, k_pool, v_pool, table, _, _ = _paged_case(rng, b, h, bs, d, mb,
                                                 lq, False)
    q_pos = np.array([[20], [-1], [9]], np.int32) + np.zeros(
        (1, lq), np.int32)
    got = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, q_pos=jnp.asarray(q_pos),
        route="pallas"))
    want = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, q_pos=jnp.asarray(np.maximum(q_pos, 0)),
        route="composition"))
    assert np.all(got[1] == 0.0)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-6)


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


class _StepSpy:
    """Stands before a pool's step executable (``fn(params, bufs,
    cache, ...)`` returning the new cache first).  After every step:
    each table row is what it was, and an inactive slot's index is what
    it was, whatever the step was shown in their place."""

    def __init__(self, fn, active_of):
        self.fn, self.active_of = fn, active_of
        self.stale = 0      # steps with an inactive slot off position 0

    def __call__(self, *args):
        before = [(np.asarray(c.table), np.asarray(c.index))
                  for c in args[2]]
        active = np.asarray(self.active_of(args)).astype(bool)
        out = self.fn(*args)
        for (table, index), c in zip(before, out[0]):
            np.testing.assert_array_equal(np.asarray(c.table), table)
            np.testing.assert_array_equal(np.asarray(c.index)[~active],
                                          index[~active])
        self.stale += bool((index[~active] > 0).any())
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _parent_masked_tables(self, cache, active):
    # GenerationPool._masked_tables before the index was masked too
    import jax.numpy as jnp

    scratch = jnp.asarray(self._scratch_row)[:, None]
    return [c._replace(table=jnp.where(active[:, None], c.table, scratch))
            for c in cache]


def _blockdiff_model():
    from paddle_tpu.models import BlockDiffusionMoELM

    pt.seed(3)
    m = BlockDiffusionMoELM(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, expert_size=32, num_experts=4,
        top_k=2, block_length=4, mask_token_id=255, denoise_steps=2)
    m.eval()
    return m


@pytest.mark.parametrize("kind", ["churn", "chunked", "speculative",
                                  "blockdiff"])
def test_masked_index_is_restored_and_tokens_are_the_parents(
        model, monkeypatch, kind):
    # an inactive slot is shown to the step with its table on scratch
    # AND its index at 0 (the kernel walks what the index reaches); the
    # returned cache has both as they were: a free slot's stale length,
    # a slot mid-prompt under chunked prefill, a slot between requests
    # of a verify chunk or a block step.  Tokens are those of the pool
    # that masked the table alone
    from paddle_tpu.inference import BlockDiffusionPool, SpeculativePool

    rng = np.random.RandomState(14)
    paged = dict(max_len=64, buckets=[16, 32], cache_layout="paged",
                 block_size=8, route="pallas")
    lens, budgets = (5, 11, 7, 3, 14), (6,) * 5
    active_of = lambda args: args[4]                        # noqa: E731
    attr = "_decode_jit"
    if kind == "churn":
        build = lambda: GenerationPool(model, slots=2, **paged)  # noqa
    elif kind == "chunked":
        # a 40-token prompt takes five chunks beside a decoding slot:
        # four steps see it inactive at positions 8..32
        lens = (5, 40, 7)
        build = lambda: GenerationPool(                     # noqa: E731
            model, slots=2, prefill_chunk_tokens=8,
            **dict(paged, buckets=[64]))
    elif kind == "speculative":
        attr = "_verify_jit"
        build = lambda: SpeculativePool(                    # noqa: E731
            model, model, spec_k=3, slots=2, **paged)
    else:
        bmodel = _blockdiff_model()
        # the second request outlasts the others: its slot steps on
        # beside a finished one
        lens, budgets = (8, 13, 6), (4, 16, 8)
        # block_step(params, bufs, cache, carry, ctl): ``live`` is the
        # column before the last (``take``)
        active_of = lambda args: np.asarray(args[4])[:, -2] != 0  # noqa
        build = lambda: BlockDiffusionPool(                 # noqa: E731
            bmodel, slots=2, cache_dtype="float32", **paged)
    prompts = [rng.randint(0, 128, (n,)).astype("int32") for n in lens]

    def run(spied):
        pool = build()
        spy = _StepSpy(getattr(pool, attr), active_of)
        if spied:
            setattr(pool, attr, spy)
        work = list(zip(prompts, budgets))
        rids = [pool.submit(p, n) for p, n in work[:2]]
        for _ in range(3):
            pool.step()
        rids += [pool.submit(p, n) for p, n in work[2:]]
        res = pool.run()
        return [res[r] for r in rids], spy.stale

    toks, stale = run(True)
    assert stale > 0, "no step saw an inactive slot with a stale index"
    monkeypatch.setattr(GenerationPool, "_masked_tables",
                        _parent_masked_tables)
    parents, _ = run(False)
    for a, b in zip(toks, parents):
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
