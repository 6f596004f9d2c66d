"""Paged (block-table) KV cache — the vLLM scheme on static shapes.

Pins the contracts the paged layout lives on:

- paged and dense layouts are TOKEN-IDENTICAL under greedy decoding
  (DecodeSession.generate and GenerationPool.run) across randomized
  prompt lengths, interleaved submit/step orders, and slot churn;
- the paged session still compiles exactly two functions per
  (bucket, decode) pair — only table VALUES vary, never shapes;
- the free-list allocator reserves a request's whole worst-case span at
  admission, defers refills under block pressure instead of failing
  mid-decode, and reuses blocks freed by ``_finish`` without
  cross-request leakage;
- reachable KV bytes scale with actual tokens (paged <= dense at every
  occupancy below full max_len);
- ``paged_decode_attention`` is the gather+mask composition of the
  dense ``decode_attention`` (the math is shared, so layouts can only
  differ by float-reduction noise);
- ``paged_cache_write`` puts the bytes where the plain
  ``pool.at[phys, :, off, :].set`` put them, and the decode step makes
  nothing else of a pool's shape; ``paged_kv_write``'s in-place kernel
  (a decode-sized chunk into float pools, K and V in one call) leaves
  the same bytes, in every case and in served pools.
"""
import numpy as np
import pytest

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
def dense_sess(model):
    return DecodeSession(model, max_len=64, buckets=[16, 32])


def test_paged_session_token_identical_randomized_lengths(model,
                                                          dense_sess):
    # property: for randomized prompt lengths (and a block size that does
    # NOT divide most of them), greedy paged == greedy dense, token for
    # token — the layout changes bytes touched, never math
    paged = DecodeSession(model, max_len=64, buckets=[16, 32],
                          cache_layout="paged", block_size=8)
    rng = np.random.RandomState(0)
    for length in rng.randint(1, 31, size=6):
        ids = rng.randint(0, 128, (2, int(length))).astype("int32")
        np.testing.assert_array_equal(
            paged.generate(ids, 6), dense_sess.generate(ids, 6),
            err_msg="prompt length %d" % length)


def test_paged_session_exactly_two_compiles(model):
    # the acceptance contract: paging must not cost compilations — the
    # block table is DATA, so one prefill bucket + one decode step
    sess = DecodeSession(model, max_len=64, buckets=[16],
                         cache_layout="paged", block_size=8)
    rng = np.random.RandomState(1)
    for length in (5, 9, 16):
        sess.generate(rng.randint(0, 128, (1, length)).astype("int32"), 4)
    assert sess.compile_counts() == {"prefill": 1, "decode": 1}


def test_paged_ragged_final_block(model, dense_sess):
    # max_len 64 with block_size 24: ceil -> 3 blocks cover 72 >= 64
    # positions; the over-hang is masked, never attended
    paged = DecodeSession(model, max_len=64, buckets=[32],
                          cache_layout="paged", block_size=24)
    rng = np.random.RandomState(2)
    ids = rng.randint(0, 128, (1, 20)).astype("int32")
    np.testing.assert_array_equal(paged.generate(ids, 8),
                                  dense_sess.generate(ids, 8))


def test_pool_paged_matches_dense_interleaved_submit_step(model,
                                                          dense_sess):
    # interleaved submit/step: requests arrive while the pool is
    # mid-decode, so refills splice into a HOT block pool
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 11, 7, 3, 14)]
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16, 32],
                          cache_layout="paged", block_size=8)
    rids = [pool.submit(p, 6) for p in prompts[:2]]
    for _ in range(3):
        pool.step()
    rids += [pool.submit(p, 6) for p in prompts[2:]]
    results = pool.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(results[rid],
                                      dense_sess.generate(p[None], 6)[0])
    counts = pool.compile_counts()
    assert counts["pool_decode"] == 1 and counts["slot_insert"] == 1


def test_pool_block_reuse_no_cross_request_leakage(model, dense_sess):
    # a pool with barely more blocks than one request: every later
    # request decodes through blocks freed by an earlier _finish, so any
    # missed table masking / stale write corrupts its tokens
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (9, 13, 6, 11)]
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                          cache_layout="paged", block_size=8,
                          num_blocks=5)  # 4 allocatable = one 16+8 req +1
    outs = pool.generate(prompts, 8)
    for p, got in zip(prompts, outs):
        np.testing.assert_array_equal(got,
                                      dense_sess.generate(p[None], 8)[0])
    stats = pool.cache_stats()
    assert stats["mapped_blocks"] == 0 and stats["free_blocks"] == 4


def test_pool_admission_defers_not_fails(model):
    # two requests that cannot coexist in the block budget: the second
    # waits in the queue (backpressure), neither fails, both finish
    rng = np.random.RandomState(5)
    a = rng.randint(0, 128, (10,)).astype("int32")
    b = rng.randint(0, 128, (12,)).astype("int32")
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                          cache_layout="paged", block_size=8,
                          num_blocks=4)  # 3 allocatable; each req needs 3
    ra, rb = pool.submit(a, 6), pool.submit(b, 6)
    pool.step()  # admits only `a`
    assert len(pool._active) == 1
    results = pool.run()
    assert set(results) == {ra, rb}
    sess = DecodeSession(model, max_len=64, buckets=[16])
    np.testing.assert_array_equal(results[ra], sess.generate(a[None], 6)[0])
    np.testing.assert_array_equal(results[rb], sess.generate(b[None], 6)[0])


def test_pool_submit_rejects_unservable_request(model):
    # a request that could NEVER fit the pool must fail at submit (the
    # queue would otherwise stall forever), and the error must be
    # actionable: blocks needed, blocks available, the knobs to turn
    pool = GenerationPool(model, max_len=64, slots=1, buckets=[16],
                          cache_layout="paged", block_size=8,
                          num_blocks=3)  # 2 allocatable blocks = 16 toks
    with pytest.raises(InvalidArgumentError, match="num_blocks"):
        pool.submit(np.zeros(10, np.int32), 20)
    # within budget still serves
    out = pool.generate([np.zeros(5, np.int32)], 3)
    assert out[0].shape == (3,)


def test_pool_rejects_num_blocks_with_dense_layout(model):
    with pytest.raises(InvalidArgumentError, match="paged"):
        GenerationPool(model, max_len=32, slots=1, buckets=[8],
                       num_blocks=4)


def test_cache_stats_reachable_bytes_track_allocator(model):
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                          cache_layout="paged", block_size=8)
    pool.submit(np.zeros(9, np.int32), 4)  # reserves ceil(13/8) = 2
    pool.step()
    stats = pool.cache_stats()
    assert stats["cache_layout"] == "paged"
    assert stats["mapped_blocks"] == 2  # ceil((9 + 4) / 8)
    assert stats["reachable_bytes"] == kv_reachable_bytes(
        [9 + 4], max_len=64, num_layers=2, num_heads=4, head_dim=16,
        layout="paged", block_size=8)
    assert stats["reachable_bytes"] < stats["dense_equiv_bytes"]
    pool.run()
    assert pool.cache_stats()["mapped_blocks"] == 0


def test_kv_reachable_bytes_paged_leq_dense_below_full():
    dims = dict(max_len=640, num_layers=4, num_heads=8, head_dim=64)
    # includes block sizes that do NOT divide max_len: the ragged final
    # block's over-hang is masked, so it must not be counted reachable
    for bs in (16, 24, 32, 48, 64, 128, 600):
        for tokens in (1, 17, 100, 320, 512, 639, 640):
            dense = kv_reachable_bytes([tokens] * 4, layout="dense",
                                       **dims)
            paged = kv_reachable_bytes([tokens] * 4, layout="paged",
                                       block_size=bs, **dims)
            assert paged <= dense, (bs, tokens, paged, dense)
    # and paged reaches parity only at full occupancy (bs | max_len)
    assert kv_reachable_bytes([640], layout="paged", block_size=32,
                              max_len=640, num_layers=4, num_heads=8,
                              head_dim=64) == \
        kv_reachable_bytes([640], layout="dense", max_len=640,
                           num_layers=4, num_heads=8, head_dim=64)


def test_paged_decode_attention_matches_dense_composition():
    # op-level: gather-through-table + mask == dense decode_attention on
    # the materialized cache; the masked over-hang past `lengths` and
    # the scratch-pointing trailing table entries contribute nothing
    import jax.numpy as jnp

    from paddle_tpu.ops import decode_attention, paged_decode_attention

    rng = np.random.RandomState(6)
    b, h, bs, d, mb = 3, 2, 8, 16, 4
    nb = 1 + b * mb
    k_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    v_pool = rng.randn(nb, h, bs, d).astype(np.float32)
    table = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    lengths = np.array([5, 17, 32], np.int32)
    q = rng.randn(b, h, 1, d).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table), lengths=jnp.asarray(lengths)))
    # dense reference: materialize each row's cache in logical order
    s = mb * bs
    k_dense = k_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    v_dense = v_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    neg = np.finfo(np.float32).min
    bias = np.where(np.arange(s)[None, :] < lengths[:, None], 0.0,
                    neg)[:, None, None, :].astype(np.float32)
    want = np.asarray(decode_attention(
        jnp.asarray(q), jnp.asarray(k_dense), jnp.asarray(v_dense),
        bias=jnp.asarray(bias)))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # garbage in masked positions must not leak: poison them and re-run
    k_poison = k_pool.copy()
    k_poison[0] = 1e9  # the scratch block
    got2 = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_poison), jnp.asarray(v_pool),
        jnp.asarray(table), lengths=jnp.asarray(lengths)))
    np.testing.assert_allclose(got2, want, atol=1e-6)


def _write_addresses(table, idx, length, bs):
    """``(phys, off)`` [B, L] of a chunk, as ``_paged_decode_forward``
    hands them to the write: a scalar index is the aligned batch
    (every row the same positions), a vector the slot-batched step,
    where a position past the table's span goes to the scratch block."""
    b, mb = table.shape
    if np.ndim(idx) == 0:
        pos = np.broadcast_to(idx + np.arange(length), (b, length))
        return table[np.arange(b)[:, None], pos // bs], pos % bs
    pos = np.asarray(idx)[:, None] + np.arange(length)[None, :]
    logical = np.minimum(pos // bs, mb - 1)
    phys = np.where(pos < mb * bs, table[np.arange(b)[:, None], logical],
                    0)
    return phys, pos % bs


# b rows of mb=3 blocks of bs=4; ``scratch`` rows are inactive slots,
# their table row zeroed for the step as ``_masked_tables`` does
_WRITE_CASES = {
    "per_row_L1": dict(idx=[5, 0, 11], length=1),
    "per_row_L5_verify_chunk": dict(idx=[2, 7, 3], length=5),
    "scalar_L8_across_a_block_edge": dict(idx=2, length=8),
    "int8_with_both_scale_pools": dict(idx=[5, 0, 9], length=2,
                                       int8=True),
    # row 0 sends 12, 13, 14 of a span of 12 to the scratch block
    "past_the_tables_span": dict(idx=[10, 4, 1], length=5, past_span=3),
    "two_inactive_rows_one_scratch_offset": dict(idx=[6, 6, 1, 6],
                                                 length=1,
                                                 scratch=(1, 3)),
}


# The same writes by the in-place kernel (``ops.paged_kv_write`` under
# ``route="pallas"``, interpreted off the chip): K and V in ONE call, a
# row with its GROUP of ``g`` rows (8 float32, 16 bfloat16) of blocks of
# 32.  ``idx(g, bs)`` puts rows at offsets 0, g - 1, g and bs - 1, chunks
# across a group's edge and across a block's, a chunk past the table's
# span; each case in float32 and bfloat16, on a pool of the chunk's heads
# and on plane 1 of a pool of three planes
_KERNEL_CASES = {
    "L1_offsets_0_and_the_edges": dict(
        idx=lambda g, bs: [0, g - 1, g, bs - 1, 2 * bs + 3], length=1),
    "L4_across_a_group_and_a_block_edge": dict(
        idx=lambda g, bs: [g - 2, bs - 2, 0, g], length=4),
    "L8_the_longest_chunk": dict(
        idx=lambda g, bs: [g - 3, bs - 5, 1, 2 * bs - 8], length=8),
    "L4_past_the_tables_span": dict(
        idx=lambda g, bs: [3 * bs - 2, g - 1, 5], length=4, past_span=2),
    "inactive_rows_one_scratch_offset": dict(
        idx=lambda g, bs: [6, 6, 1, 6], length=1, scratch=(1, 3)),
}
for _name, _spec in _KERNEL_CASES.items():
    for _dtype in ("float32", "bfloat16"):
        for _planes in (1, 3):
            _WRITE_CASES["kernel_%s_%s_%s" % (
                _name, _dtype, "planed" if _planes > 1 else "whole")] = \
                dict(_spec, kernel=True, dtype=_dtype, planes=_planes, bs=32)


def _kernel_write_case(spec, rng):
    """A case of ``_KERNEL_CASES``: both pools through ``paged_kv_write``
    forced onto the kernel, against the scatter of each and the same
    thing said row by row."""
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_kv_write
    from paddle_tpu.ops.pallas_decode import write_group

    dtype, planes, bs, length = (spec[k] for k in
                                 ("dtype", "planes", "bs", "length"))
    g = write_group(bs, jnp.dtype(dtype).itemsize)
    assert g == {"float32": 8, "bfloat16": 16}[dtype]
    idx = np.asarray(spec["idx"](g, bs), np.int32)
    b, h, d, mb = len(idx), 2, 8, 3
    nb = 1 + b * mb
    table = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    table[list(spec.get("scratch", ()))] = 0
    phys, off = _write_addresses(table, idx, length, bs)
    assert (phys == 0).sum() == spec.get("past_span", 0) \
        + len(spec.get("scratch", ())) * length
    repeats = "scratch" in spec
    base = None if planes == 1 else h        # plane 1 of three
    heads = slice(None) if base is None else slice(base, base + h)
    pools = [jnp.asarray(rng.randn(nb, planes * h, bs, d) * 50, dtype)
             for _ in range(2)]
    news = [jnp.asarray(rng.randn(b, h, length, d), jnp.float32)
            for _ in range(2)]
    got = paged_kv_write(*pools, *news, jnp.asarray(phys), jnp.asarray(off),
                         head_base=base, route="pallas")
    plain = paged_kv_write(*pools, *news, jnp.asarray(phys),
                           jnp.asarray(off), head_base=base,
                           route="composition")
    for before, new, out, scattered in zip(pools, news, got, plain):
        assert out.dtype == before.dtype and out.shape == before.shape
        want = np.array(before)
        new = np.asarray(new.astype(before.dtype))
        for bi in range(b):
            for li in range(length):
                want[phys[bi, li], heads, off[bi, li]] = new[bi, :, li]
        out, scattered = np.asarray(out), np.asarray(scattered)
        rows = np.arange(1 if repeats else 0, nb)
        np.testing.assert_array_equal(out[rows], scattered[rows])
        np.testing.assert_array_equal(out[rows], want[rows])
        untouched = np.setdiff1d(np.arange(nb), phys.ravel())
        np.testing.assert_array_equal(out[untouched],
                                      np.asarray(before)[untouched])
        if repeats:
            # the scratch row holds one of the rows sent there, and the
            # group's other rows are as they were
            at = off[spec["scratch"][0], 0]
            assert any(np.array_equal(out[0, heads, at], new[bi, :, 0])
                       for bi in spec["scratch"])
            others = np.setdiff1d(np.arange(bs), [at])
            np.testing.assert_array_equal(
                out[0][:, others], np.asarray(before)[0][:, others])


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_paged_cache_write_is_the_plain_scatter_bit_for_bit(case):
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_cache_write, quantize_kv

    spec = _WRITE_CASES[case]
    rng = np.random.RandomState(sorted(_WRITE_CASES).index(case))
    if spec.get("kernel"):
        return _kernel_write_case(spec, rng)
    idx, length = spec["idx"], spec["length"]
    b = 3 if np.ndim(idx) == 0 else len(idx)
    h, bs, d, mb = 2, 4, 8, 3
    nb = 1 + b * mb
    table = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    table[list(spec.get("scratch", ()))] = 0
    phys, off = _write_addresses(table, np.asarray(idx, np.int32), length,
                                 bs)
    k_new = rng.randn(b, h, length, d).astype(np.float32)
    v_new = rng.randn(b, h, length, d).astype(np.float32)
    if spec.get("int8"):
        (k_new, k_s), (v_new, v_s) = quantize_kv(k_new), quantize_kv(v_new)
        dtype = np.int8
        pairs = [(k_new, 4), (v_new, 4), (k_s, 3), (v_s, 3)]
    else:
        dtype = np.float32
        pairs = [(k_new, 4), (v_new, 4)]
    assert (phys == 0).sum() == spec.get("past_span", 0) \
        + len(spec.get("scratch", ())) * length
    repeats = len(set(zip(phys.ravel(), off.ravel()))) < phys.size
    assert repeats == ("scratch" in spec)
    for new, rank in pairs:
        new = np.asarray(new)
        shape = (nb, h, bs, d)[:rank]
        before = (rng.randn(*shape) * 50).astype(
            dtype if rank == 4 else np.float32)
        got = np.asarray(paged_cache_write(
            jnp.asarray(before), jnp.asarray(new), jnp.asarray(phys),
            jnp.asarray(off)))
        assert got.dtype == before.dtype and got.shape == before.shape
        # what the write replaced
        if rank == 4:
            plain = jnp.asarray(before).at[phys, :, off, :].set(
                jnp.asarray(new).transpose(0, 2, 1, 3))
        else:
            plain = jnp.asarray(before).at[phys, :, off].set(
                jnp.asarray(new).transpose(0, 2, 1))
        plain = np.asarray(plain)
        # and the same thing said row by row
        want = before.copy()
        for bi in range(b):
            for li in range(length):
                want[phys[bi, li], :, off[bi, li]] = new[bi, :, li]
        # where rows repeat (only ever in the scratch block) the winner
        # is not defined; every live block is, to the bit
        rows = np.arange(1 if repeats else 0, nb)
        np.testing.assert_array_equal(got[rows], plain[rows])
        np.testing.assert_array_equal(got[rows], want[rows])
        # no write strayed: blocks no address names are as they were
        untouched = np.setdiff1d(np.arange(nb), phys.ravel())
        np.testing.assert_array_equal(got[untouched], before[untouched])
        if repeats:
            # the scratch row holds one of the rows sent there
            sent = [new[bi, :, 0] for bi in spec["scratch"]]
            assert any(np.array_equal(got[0, :, off[1, 0]], r)
                       for r in sent)


@pytest.mark.parametrize("how", ["scatter", "kernel-float32",
                                 "kernel-bfloat16"])
def test_paged_cache_write_drops_an_index_outside_the_pool(how):
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_cache_write, paged_kv_write

    rng = np.random.RandomState(0)
    dtype = how.partition("-")[2] or "float32"
    before = jnp.asarray(rng.randn(5, 2, 4, 8), dtype)
    new = jnp.asarray(rng.randn(2, 2, 1, 8), dtype)
    phys = np.array([[5], [2]], np.int32)       # 5 is one past the pool
    off = np.array([[1], [3]], np.int32)
    if how == "scatter":
        got = paged_cache_write(before, new, jnp.asarray(phys),
                                jnp.asarray(off))
    else:
        # the kernel guards the copy: no group is read or written for
        # the row, in either pool
        got, other = paged_kv_write(before, before + 1, new, new,
                                    jnp.asarray(phys), jnp.asarray(off),
                                    route="pallas")
        np.testing.assert_array_equal(np.asarray(other)[[0, 1, 3, 4]],
                                      np.asarray(before + 1)[[0, 1, 3, 4]])
    want = np.array(before)
    want[2, :, 3] = np.asarray(new)[1, :, 0]    # nothing clamped onto 4
    np.testing.assert_array_equal(np.asarray(got), want)


def test_the_write_kernel_takes_its_slots_a_grid_step_at_a_time(monkeypatch):
    # more groups than the kernel's VMEM budget holds (a verify chunk on a
    # wide pool: ``tests/test_tpu_compile.py``'s gpt-verify) go a few
    # slots a grid step; a budget of one slot's groups makes every slot a
    # step of its own, and the pools come out the same
    from paddle_tpu.ops import pallas_decode

    spec = _WRITE_CASES[
        "kernel_L4_across_a_group_and_a_block_edge_float32_planed"]
    whole = lambda: _kernel_write_case(spec, np.random.RandomState(3))
    whole()
    h, g, d, length = 2, 8, 8, spec["length"]
    monkeypatch.setattr(pallas_decode, "_KV_VMEM_BUDGET",
                        2 * length * h * g * d * 4)
    pallas_decode._kv_write_call.clear_cache()
    try:
        whole()
    finally:
        monkeypatch.undo()
        pallas_decode._kv_write_call.clear_cache()


def test_the_write_keeps_the_scatter_where_the_kernel_is_not_for(
        monkeypatch):
    # the route, from what the call can see: a prefill-shaped chunk and an
    # int8 pool keep the scatter under every route; a float pool's
    # decode-sized chunk takes the kernel when forced, and under "auto"
    # on a TPU where the geometry compiles (a head of half a lane tile
    # does not: the forced route raises its refusal)
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")

    def pool(dtype, bs=32, d=128):
        return jax.ShapeDtypeStruct((9, 2, bs, d), jnp.dtype(dtype))

    fa.reset_backend_memo()
    try:
        for route, chunk, dtype, want in (
                ("auto", 1, "float32", "scatter"),        # the CPU
                ("composition", 1, "float32", "scatter"),
                ("pallas", 1, "float32", "kernel"),
                ("pallas", 8, "bfloat16", "kernel"),
                ("pallas", 9, "bfloat16", "scatter"),
                ("pallas", 1, "int8", "scatter")):
            assert fa.paged_kv_write_route(pool(dtype), chunk, route) \
                == want, (route, chunk, dtype)
        with fa.decode_route("pallas"):
            assert fa.paged_kv_write_route(pool("float32"), 4) == "kernel"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fa.reset_backend_memo()
        assert fa.paged_kv_write_route(pool("bfloat16"), 1) == "kernel"
        assert fa.paged_kv_write_route(pool("bfloat16"), 128) == "scatter"
        assert fa.paged_kv_write_route(pool("int8"), 1) == "scatter"
        # blocks of 8 are half a packed tile of bfloat16 rows
        assert fa.paged_kv_write_route(pool("bfloat16", bs=8), 1) \
            == "scatter"
        assert fa.paged_kv_write_route(pool("float32", d=64), 1) \
            == "scatter"
        with pytest.raises(InvalidArgumentError, match="lane"):
            fa.paged_kv_write_route(pool("float32", d=64), 1, "pallas")
    finally:
        fa.reset_backend_memo()


def _served_model(kind):
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    if kind == "gpt":
        return _tiny_model(), {}
    from harness import cca_weights, looped_weights
    from paddle_tpu.models import CCAMoELM, LoopedLM

    name, build, weights = {
        "looped": ("toy-looped.json", LoopedLM, looped_weights),
        "cca": ("toy-cca.json", CCAMoELM, cca_weights)}[kind]
    with open(os.path.join(bench, "configs", name)) as f:
        cfg = json.load(f)
    pt.seed(0)
    m = build(**weights.model_kwargs(cfg))
    m.eval()
    weights.load_into(m, cfg, 11)
    return m, {"vocab": cfg["vocab_size"]}


@pytest.mark.parametrize("kind", ["gpt", "looped", "cca"])
def test_a_served_run_on_the_write_kernel_leaves_the_scatters_bytes(
        kind, monkeypatch):
    # a pool served under ``route="pallas"`` (the attention AND the write
    # on their kernels, interpreted) against the same pool with the write
    # alone sent back to the scatters: every token and every byte of
    # every K/V pool but the scratch block (where inactive slots land in
    # no defined order).  Blocks of 16 and float32 pools: a group is half
    # a block.  Against ``route="composition"`` the tokens are the same
    # too; its pools differ in the last bits by the attention's route, not
    # the write's
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    model, info = _served_model(kind)
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, info.get("vocab", 128), (n,)).astype("int32")
               for n in (5, 17, 9, 14)]

    def serve(route):
        pool = GenerationPool(model, max_len=64, slots=3, buckets=[32],
                              cache_layout="paged", block_size=16,
                              route=route)
        outs = pool.generate(prompts, 12)
        pools = [np.asarray(x)[1:] for entry in pool._cache
                 if hasattr(entry, "table") for x in (entry.k, entry.v)]
        return outs, pools, pool

    toks, pools, pool = serve("pallas")
    assert pool._entries_meta["kv_write"] == "kernel"
    assert len(pools) == 2 * pool._entries_meta["kv_entries"]
    monkeypatch.setattr(fa, "paged_kv_write_route",
                        lambda *a, **k: "scatter")
    want_toks, want_pools, _ = serve("pallas")
    for got, want in zip(toks, want_toks):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(pools, want_pools):
        np.testing.assert_array_equal(got, want)
    monkeypatch.undo()
    plain_toks, _, plain = serve("composition")
    assert plain._entries_meta["kv_write"] == "scatter"
    for got, want in zip(toks, plain_toks):
        np.testing.assert_array_equal(got, want)


def test_pool_decode_makes_nothing_pool_shaped_but_the_writes(model):
    # structural, from a CPU lowering: per layer the only operations
    # whose result has a K/V pool's shape are the two writes, each a
    # scatter whose window is head_dim alone.  It guards the SOURCE
    # against a transpose, select or copy of a pool creeping back in;
    # what layout the TPU then picks is chip_smoke.py's to check
    import jax
    from jax._src.lib.mlir import ir

    pool = GenerationPool(model, max_len=48, slots=3, buckets=[16],
                          cache_layout="paged", block_size=8,
                          num_blocks=11)
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    lowered = jax.jit(pool._pool_decode).lower(
        params, bufs, pool._cache, np.zeros(n, np.int32),
        np.ones(n, bool), samp, np.zeros(n, np.uint32),
        np.zeros(n, np.int32))
    shape = list(pool._cache[0].k.shape)
    assert shape == [11, 4, 8, 16]      # no other array of the step's
    made, windows = [], []

    def visit(op):
        if any(isinstance(r.type, ir.RankedTensorType)
               and list(r.type.shape) == shape for r in op.results):
            made.append(op.name)
            if op.name == "stablehlo.scatter":
                windows.append(
                    str(op.attributes["scatter_dimension_numbers"]))
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir(dialect="stablehlo").operation.walk(visit)
    assert made == ["stablehlo.scatter"] * (2 * len(pool._cache))
    for dims in windows:
        assert "inserted_window_dims = [0, 1, 2]" in dims, dims


def test_paged_decode_attention_gate_conditions(monkeypatch):
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    ok_q, bs = (1, 8, 1, 128), 128
    nb = fa.DECODE_FLASH_MIN_CACHE // bs
    # the gate memoizes the backend lookup; clear it around the
    # monkeypatch so the fake backend is seen and cannot leak
    fa.reset_backend_memo()
    try:
        # CPU backend: the "auto" route never engages the kernel
        assert not fa.paged_decode_attention_supported(ok_q, bs, nb,
                                                       jnp.float32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fa.reset_backend_memo()
        assert fa.paged_decode_attention_supported(ok_q, bs, nb,
                                                   jnp.bfloat16)
        # below the measured-crossover pool size: composition wins
        assert not fa.paged_decode_attention_supported(ok_q, bs, nb - 1,
                                                       jnp.bfloat16)
        # sublane-hostile block size
        assert not fa.paged_decode_attention_supported(ok_q, 12, nb,
                                                       jnp.bfloat16)
        # long query chunks belong to the prefill kernel path
        assert not fa.paged_decode_attention_supported((1, 8, 9, 128),
                                                       bs, nb,
                                                       jnp.bfloat16)
        # the pools stay in HBM and an entry is copied by hand: a head
        # of half a lane tile cannot be; blocks of 32 can, with or
        # without an int8 pool's scales or a bias (no argument of the
        # gate: they add no rule)
        assert not fa.paged_decode_attention_supported((1, 8, 1, 64), bs,
                                                       nb, jnp.bfloat16)
        assert fa.paged_decode_attention_supported(ok_q, 32, 4 * nb,
                                                   jnp.bfloat16)
    finally:
        fa.reset_backend_memo()


def test_gen_decode_cache_paged_validation(model):
    with pytest.raises(InvalidArgumentError, match="layout"):
        model.gen_decode_cache(1, 32, layout="sparse")
    with pytest.raises(InvalidArgumentError, match="block_size"):
        model.gen_decode_cache(1, 32, layout="paged", block_size=0)
    with pytest.raises(InvalidArgumentError, match="num_blocks"):
        model.gen_decode_cache(1, 32, layout="paged", block_size=8,
                               num_blocks=1)
    cache = model.gen_decode_cache(2, 32, layout="paged", block_size=8)
    # identity mapping, scratch block 0 reserved
    assert cache[0].k.shape[0] == 1 + 2 * 4
    assert np.asarray(cache[0].table).min() == 1
    # explicit num_blocks -> allocator-managed: table starts unmapped
    cache = model.gen_decode_cache(2, 32, layout="paged", block_size=8,
                                   num_blocks=6)
    assert np.asarray(cache[0].table).max() == 0


@pytest.mark.slow
def test_pool_paged_slot_churn_randomized_sweep(model, dense_sess):
    # sweep-sized churn property: many random interleavings of
    # submit/step with mixed lengths and budgets over a TIGHT pool —
    # every request must still match its standalone dense generation
    rng = np.random.RandomState(7)
    pool = GenerationPool(model, max_len=64, slots=3, buckets=[16, 32],
                          cache_layout="paged", block_size=8,
                          num_blocks=10)
    expect = {}
    pending = 14
    while pending or expect:
        if pending and (rng.rand() < 0.5 or not expect):
            n = int(rng.randint(1, 30))
            p = rng.randint(0, 128, (n,)).astype("int32")
            m = int(rng.randint(1, min(8, 64 - n) + 1))
            rid = pool.submit(p, m)
            expect[rid] = dense_sess.generate(p[None], m)[0]
            pending -= 1
        else:
            pool.step()
            done = set(pool._results) & set(expect)
            for rid in done:
                np.testing.assert_array_equal(pool._results[rid],
                                              expect.pop(rid))
    results = pool.run()
    for rid, want in expect.items():
        np.testing.assert_array_equal(results[rid], want)
