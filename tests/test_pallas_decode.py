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
    # the paged walk copies an entry out of HBM by hand: whole lanes in
    # the pools' minor dimension (an int8 pool's scales and a bias are
    # laid out by the walk's steps, and add no rule)
    for args, match in (((64, 32), "head_dim 64 is not whole"),
                        ((128, 12), "multiple of the 8"),
                        ((48, 32), "head_dim 48")):
        assert match in pd.paged_mosaic_refusal(*args)
    for args in ((128, 32), (256, 8), (128, 128)):
        assert pd.paged_mosaic_refusal(*args) is None
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


# ---------------------------------------------------------------------------
# the walk over a row's live entries, several a step (docs/DESIGN.md 5l)
# ---------------------------------------------------------------------------

# a benchmark cell's call, scaled down in rows and heads, not in the block,
# the group or the chunk: (query heads, K/V heads, Lq, block, head size,
# table entries, pool type, the tile its shapes give)
_CELLS = {
    "zaya": (8, 2, 1, 128, 128, 24, "bfloat16", 8),
    "gpt": (4, 4, 1, 32, 128, 32, "float32", 8),
    "jamba": (20, 1, 1, 128, 128, 18, "bfloat16", 8),
    "sdar": (16, 2, 8, 128, 128, 20, "bfloat16", 8),     # two blocks of 4
}


def _cell_case(rng, name, b):
    import jax.numpy as jnp

    hq, hkv, lq, bs, d, mb, dtype, tile = _CELLS[name]
    nb = 1 + b * mb
    q = jnp.asarray(rng.randn(b, hq, lq, d), dtype)
    k_pool, v_pool = (jnp.asarray(rng.randn(nb, hkv, bs, d), dtype)
                      for _ in range(2))
    # a row's blocks lie anywhere in the pool
    table = jnp.asarray(1 + rng.permutation(b * mb).reshape(b, mb),
                        jnp.int32)
    rows = (hq // hkv) * lq
    assert pd.paged_tile_entries(
        pd.head_chunk(hkv, bs, d, q.dtype.itemsize), rows, bs, d,
        q.dtype.itemsize, mb) == tile
    return q, k_pool, v_pool, table, lq, bs, mb, tile


@pytest.mark.parametrize("ends", ["entry", "tile", "ragged", "whole"])
@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_the_walk_matches_the_composition_at_the_cells_geometries(cell,
                                                                  ends):
    # lengths that end exactly on an entry's boundary, on a tile's, in
    # the middle of an entry, and at the table's end (jamba's 18 and
    # sdar's 20 entries are widths no tile of 8 divides)
    import jax.numpy as jnp

    rng = np.random.RandomState(21)
    b = 4
    q, k_pool, v_pool, table, lq, bs, mb, tile = _cell_case(rng, cell, b)
    held = {"entry": [bs, 3 * bs, (tile + 1) * bs, 2 * bs],
            "tile": [tile * bs, 2 * tile * bs, tile * bs, tile * bs],
            "ragged": [lq, bs + 7, tile * bs + bs // 2, mb * bs - 5],
            "whole": [mb * bs] * b}[ends]
    q_pos = jnp.asarray([[n - lq + t for t in range(lq)] for n in held],
                        jnp.int32)
    got = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, q_pos=q_pos,
        route="pallas").astype(jnp.float32))
    want = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, q_pos=q_pos,
        route="composition").astype(jnp.float32))
    # bfloat16: both sides round float32 sums at the end, an ulp is 2**-8
    # of a value
    tol = 4e-6 if q.dtype == jnp.float32 else 2.0 ** -6
    np.testing.assert_allclose(got, want, atol=tol)


@pytest.mark.parametrize("lq", [1, 4, 8])
@pytest.mark.parametrize("form,bs,tile", [
    ("int8", 128, 5), ("bias", 128, 5), ("bias-one-head", 128, 5),
    ("chunks", 128, 2), ("int8", 32, 5), ("bias", 32, 5),
    ("bias-one-head", 8, 5), ("int8", 8, 1)])
def test_the_walk_with_scales_bias_and_part_head_chunks(monkeypatch, form,
                                                        bs, tile, lq):
    # the side streams: an int8 pool's scales (gathered through the
    # table) and a bias reach the kernel laid out by the walk's steps, a
    # step's share in a row of whole lanes whatever the block: blocks of
    # 128, of 32 (five entries are 160 positions in 256 lanes) and of 8
    # (an int8 entry of 8 rows is a tile of its own: 8 positions in 128
    # lanes); and a chunk of one head of three, two entries a tile (the
    # budget holds two heads' blocks, 3 has no divisor 2)
    import jax.numpy as jnp

    rng = np.random.RandomState(22)
    b, d, mb = 3, 16, 5
    h = 3 if form == "chunks" else 8
    quant = form == "int8"
    if form == "chunks":
        _kv_budget(monkeypatch, 2, bs, d, 4)
        assert pd.head_chunk(h, bs, d, 4) == 1
    hc = pd.head_chunk(h, bs, d, 1 if quant else 4, quant)
    assert pd.paged_tile_entries(hc, lq, bs, d, 1 if quant else 4,
                                 mb) == tile
    q, k_pool, v_pool, table, ks, vs = _paged_case(rng, b, h, bs, d, mb, lq,
                                                   quant)
    held = np.array([bs + 3, mb * bs, 2 * bs], np.int32)
    kwargs = dict(k_scale=ks, v_scale=vs, q_pos=jnp.asarray(
        held[:, None] - lq + np.arange(lq, dtype=np.int32)))
    if form.startswith("bias"):
        hb = 1 if form == "bias-one-head" else h
        bias = rng.randn(b, hb, lq, mb * bs).astype(np.float32)
        bias[rng.rand(*bias.shape) < 0.2] = np.finfo(np.float32).min
        bias[..., 0] = 0.0
        kwargs["bias"] = jnp.asarray(bias)
    got = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, route="pallas", **kwargs))
    want = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, route="composition", **kwargs))
    np.testing.assert_allclose(got, want, atol=4e-6)
    pd._paged_call.clear_cache()


@pytest.mark.parametrize("hc,rows,bs,d,itemsize,mb,want", [
    (2, 4, 128, 128, 2, 24, 8),     # zaya: 512 KB an entry
    (16, 1, 32, 128, 4, 32, 2),     # gpt: 1.5 MB an entry
    (1, 20, 128, 128, 2, 18, 8),    # jamba: the cap, not the budget
    (4, 32, 128, 128, 2, 20, 4),    # sdar: 1 MB an entry
    (4, 64, 128, 128, 2, 20, 4),    # a verify chunk of 8 there
    (16, 1, 32, 128, 1, 32, 5),     # gpt's pool in int8: 768 KB an entry
    (8, 4, 128, 128, 1, 16, 2),     # int8 in blocks of 128: 1.5 MB
    (8, 1, 16, 128, 1, 16, 1),      # int8 rows off its sublanes of 32
    (4, 1, 8, 128, 2, 8, 1),        # bfloat16 rows off its sublanes
    (2, 1, 8, 16, 4, 4, 4),         # the toy: the table's width
    (32, 8, 128, 256, 4, 64, 1),    # an entry that fills the budget
], ids=["zaya", "gpt", "jamba", "sdar", "sdar-verify", "int8-32",
        "int8-128", "int8-16", "bf16-8", "toy", "full"])
def test_tile_entries_come_from_shapes(hc, rows, bs, d, itemsize, mb, want):
    assert pd.paged_tile_entries(hc, rows, bs, d, itemsize, mb) == want


def test_a_row_that_sees_nothing_between_two_live_rows():
    # the first tile of the row after is started while the row before
    # computes its last: with an empty row between them no copy may land
    # in it, and none may be lost.  Every buffer's turn is taken in order
    # (an odd number of tiles before the gap, an even one after)
    import jax.numpy as jnp

    rng = np.random.RandomState(23)
    b, h, bs, d, mb, lq = 6, 2, 8, 16, 12, 1
    q, k_pool, v_pool, table, _, _ = _paged_case(rng, b, h, bs, d, mb, lq,
                                                 False)
    assert pd.paged_tile_entries(2, 1, bs, d, 4, mb) == 8
    held = np.array([0, 70, 0, 0, 90, 30], np.int32)
    q_pos = jnp.asarray(held[:, None] - 1)
    got = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, q_pos=q_pos, route="pallas"))
    want = np.asarray(fa.paged_decode_attention(
        q, k_pool, v_pool, table, q_pos=jnp.maximum(q_pos, 0),
        route="composition"))
    assert np.all(got[held == 0] == 0.0)
    np.testing.assert_allclose(got[held > 0], want[held > 0], atol=2e-6)
    # and a call of empty rows alone copies nothing and emits zeros
    none = np.asarray(fa.paged_decode_attention(
        q, jnp.full_like(k_pool, jnp.nan), jnp.full_like(v_pool, jnp.nan),
        table, q_pos=jnp.full((b, lq), -1, jnp.int32), route="pallas"))
    assert np.all(none == 0.0)


@pytest.mark.parametrize("case", ["float", "int8", "bias"])
def test_an_entry_of_the_last_tile_past_the_rows_reach_changes_no_bit(case):
    # rows that end inside their FIRST tile, the entries behind them NaN
    # in K, V, scales and bias.  No such entry of K or V is copied (0 x
    # NaN would be in the sums): what a buffer holds there is an older
    # entry's values under a probability of exactly 0.  The scales and
    # the bias come a step's width at a time, dead positions with them:
    # K's scales and the bias fall to the mask's select, V's scales are
    # 0 past the row's reach.  The result is the clean pool's to the bit
    import jax.numpy as jnp

    rng = np.random.RandomState(24)
    b, h, bs, d, mb, lq = 3, 8, 128, 16, 6, 2
    quant = case == "int8"
    q, k_pool, v_pool, table, ks, vs = _paged_case(rng, b, h, bs, d, mb, lq,
                                                   quant)
    assert pd.paged_tile_entries(8, lq, bs, d, 1 if quant else 4,
                                 mb) == 6
    last = np.array([3, 0, 4])
    q_pos = jnp.asarray(last[:, None] * bs + np.array([[5, 6]]), jnp.int32)
    kwargs = dict(q_pos=q_pos, k_scale=ks, v_scale=vs)
    bias = rng.randn(b, 1, lq, mb * bs).astype(np.float32)
    if case == "bias":
        kwargs["bias"] = jnp.asarray(bias)
    clean = fa.paged_decode_attention(q, k_pool, v_pool, table,
                                      route="pallas", **kwargs)
    dead_cols = np.arange(mb)[None, :] > last[:, None]
    dead = np.asarray(table)[dead_cols]
    if quant:
        kwargs.update(k_scale=ks.at[dead].set(jnp.nan),
                      v_scale=vs.at[dead].set(jnp.nan))
    else:
        k_pool = k_pool.at[dead].set(jnp.nan)
        v_pool = v_pool.at[dead].set(jnp.nan)
    if case == "bias":
        bias[np.broadcast_to(np.repeat(dead_cols, bs, 1)[:, None, None, :],
                             bias.shape)] = np.nan
        kwargs["bias"] = jnp.asarray(bias)
    got = np.asarray(fa.paged_decode_attention(q, k_pool, v_pool, table,
                                               route="pallas", **kwargs))
    assert np.isfinite(got).all(), "a dead entry reached the arithmetic"
    np.testing.assert_array_equal(got, np.asarray(clean))
