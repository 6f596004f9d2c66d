"""The sparse-expert decoder that generates by diffusion over blocks, at a
size the CPU holds (width 64, 4 query heads on 2 K/V heads, 8 experts top-2,
block 4, vocabulary 512), against the plain reference under
``benchmark/harness`` on seeded weights.

Logits are compared, not sampled tokens.  Tolerance ``TOL``: both sides
compute in float32 on the CPU and differ only in the order of their sums
(the program's grouped matmul, folded heads and float32 combine against
the reference's per-head and per-expert loops); over two layers of width
64 that is a few 1e-6 of a logit scale of 1, so 1e-4 leaves room and is
still far under the 1e-2 a wrong mask, position, gate or store shows.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import blockdiff_reference as ref  # noqa: E402
from harness import blockdiff_weights as bw  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.core.errors import (InvalidArgumentError,  # noqa: E402
                                    PreconditionNotMetError)
from paddle_tpu.inference import BlockDiffusionPool  # noqa: E402
from paddle_tpu.inference.block_diffusion import commit_plan  # noqa: E402
from paddle_tpu.models import BlockDiffusionMoELM  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402

TOL = 1e-4
CFG = dict(vocab_size=512, hidden_size=64, num_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
           rope_theta=1e6, rms_norm_eps=1e-6, block_length=4,
           mask_token_id=511, denoise_steps=2, weights_dtype="float32")
SEED = 7


@pytest.fixture(scope="module")
def model():
    m = BlockDiffusionMoELM(**bw.model_kwargs(CFG))
    m.eval()
    bw.load_into(m, CFG, SEED)
    return m


@pytest.fixture(scope="module")
def weights():
    return bw.make_weights(CFG, SEED)


SIZES = bw.sizes(CFG)


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 511, n)


def test_full_forward_under_the_block_causal_mask(model, weights):
    ids = prompt_of(23)
    got = np.asarray(model(pt.to_tensor(ids[None])).value)[0]
    want = np.asarray(ref.forward_logits(weights, ids, SIZES))
    assert np.abs(got - want).max() < TOL
    # and the mask is the block's: a change inside a row's own block
    # moves its logits, a change in a later block does not
    later = ids.copy()
    later[12] = (later[12] + 1) % 511
    moved = np.asarray(model(pt.to_tensor(later[None])).value)[0]
    assert np.abs(moved[:12] - got[:12]).max() == 0.0
    same_block = ids.copy()
    same_block[11] = (same_block[11] + 1) % 511
    moved = np.asarray(model(pt.to_tensor(same_block[None])).value)[0]
    assert np.abs(moved[8] - got[8]).max() > 1e-3


def pool_of(model, layout, **kw):
    if layout == "paged":
        kw.setdefault("block_size", 8)
    return BlockDiffusionPool(model, 64, slots=3, buckets=[16, 32],
                              cache_layout=layout, cache_dtype="float32",
                              **kw)


def prefilled(pool, prompt):
    """``(params, bufs, cache)``: the batch-1 cache of the prompt's whole
    blocks, through the pool's own bucketed prefill."""
    params, bufs = pool._session._state_vals()
    padded = np.zeros((1, pool._session._bucket_for(len(prompt))), np.int32)
    padded[0, :len(prompt)] = prompt
    return params, bufs, pool._prefill_jit(
        params, bufs, jnp.asarray(padded), len(prompt) // 4 * 4)


@pytest.mark.parametrize("prompt_len", [8, 9, 11])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_and_the_two_block_forward_equal_the_references_replay(
        model, weights, layout, prompt_len):
    """Through the pool's own executables and caches: the bucketed
    prefill, then for every state of every block the forward that
    denoises it, two blocks of rows as the step runs them: a block's
    first state behind the clean block before it, whose store it carries
    (the index then moves past that block), every other state in front
    of its own repeat.  The head is asked for the state's rows alone, and
    they are held against the reference's one masked forward.  The output
    (10 tokens) ends inside a block for every prompt length here."""
    prompt, max_new = prompt_of(prompt_len), 10
    tokens, steps = ref.generate(weights, prompt, max_new, SIZES)
    rows = ref.replay_rows(prompt, tokens, steps, SIZES)
    want = np.asarray(ref.forward_logits(
        weights, rows["ids"], SIZES, pos=rows["pos"],
        allow=jnp.asarray(rows["allow"])))
    pool = pool_of(model, layout)
    params, bufs, cache = prefilled(pool, prompt)
    whole = prompt_len // 4 * 4
    run = jax.jit(lambda ids, c, last: pool._session._run_model(
        params, bufs, ids, c, last=last))
    seen, start, carried = 0, whole, 0
    clean = list(prompt) + tokens
    for st in rows["states"]:
        at = st["offset"]
        block_start = int(rows["pos"][at])
        state = rows["ids"][at:at + 4].tolist()
        assert int(np.asarray(cache[0].index).reshape(-1)[0]) == start
        if block_start != start:
            # the block before is clean: this forward carries its store
            logits, new = run(jnp.asarray([clean[start:start + 4] + state]),
                              cache, jnp.asarray([4]))
            start, carried = block_start, carried + 1
        else:
            logits, new = run(jnp.asarray([state + state]), cache,
                              jnp.asarray([0]))
        assert logits.shape == (1, 4, CFG["vocab_size"])
        assert np.abs(np.asarray(logits)[0] - want[at:at + 4]).max() < TOL
        seen += 1
        # as the pool's step leaves it: all eight rows' K/V written, the
        # index at the state's block, so that the next forward overwrites
        # what was noisy or dead
        cache = [n._replace(index=jnp.full_like(n.index, start))
                 for n in new]
    assert seen == len(rows["states"]) >= 5 and carried >= 2
    # and the pool itself serves the plain loop's tokens and steps
    got_steps = []
    pool.on_token = lambda rid, t: got_steps.append(pool.token_commit_step)
    rid = pool.submit(prompt, max_new)
    assert pool.run()[rid].tolist() == tokens
    assert got_steps == steps


# what the carried store must get right, each case a list of (prompt
# length, tokens asked for) served in turn, the slots of the pool, and the
# token (by its place in the first request's output) that is the EOS
_CASES = {
    # no whole block to the left of the first block: the prefill's cache
    # holds nothing that is attended
    "prompt_shorter_than_a_block": ([(3, 9)], 3, None),
    # the first block starts all masked, with a store before it that the
    # prefill made, not the step
    "prompt_of_whole_blocks": ([(8, 12)], 3, None),
    # no store at all: the request's one block is its last
    "request_of_one_block": ([(9, 3), (8, 4), (2, 1)], 3, None),
    "partial_last_block": ([(8, 10), (6, 7)], 3, None),
    # one slot: the second request's first forward finds the first's
    # clean last block in ``carry`` and must not store it
    "slot_refilled_after_a_clean_block": ([(8, 8), (5, 9), (4, 4), (7, 6)],
                                          1, None),
    "eos_inside_a_block": ([(9, 12), (8, 6)], 3, 5),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_carried_store_serves_the_references_tokens_and_commit_steps(
        model, weights, case):
    reqs, slots, eos_at = _CASES[case]
    want = [ref.generate(weights, prompt_of(n, 3), k, SIZES)
            for n, k in reqs]
    eos = None
    if eos_at is not None:
        eos = want[0][0][eos_at]
        assert eos_at % 4 != 3 and eos not in want[1][0]
        cut = want[0][0].index(eos) + 1
        want[0] = (want[0][0][:cut], want[0][1][:cut])
    pool = BlockDiffusionPool(model, 64, slots=slots, buckets=[16, 32],
                              cache_layout="paged", block_size=8,
                              cache_dtype="float32", eos_id=eos)
    got_steps = {}
    pool.on_token = lambda rid, t: got_steps.setdefault(rid, []).append(
        pool.token_commit_step)
    rids = [pool.submit(prompt_of(n, 3), k) for n, k in reqs]
    out = pool.run()
    for rid, (tokens, steps) in zip(rids, want):
        assert out[rid].tolist() == tokens
        assert got_steps[rid] == steps
    assert pool.compile_counts()["block_step"] == 1
    assert len(pool._free_blocks) == pool._num_blocks - 1


@pytest.mark.parametrize("prompt_len,max_new,blocks", [(8, 12, 3),
                                                       (9, 11, 3),
                                                       (6, 4, 2)])
def test_a_block_costs_its_denoising_steps_and_no_forward_more(
        model, weights, prompt_len, max_new, blocks):
    """A request of n generated blocks, each with two or more positions to
    fill, takes ``denoise_steps x n`` slot-forwards: the n - 1 stores ride
    the first steps of the blocks after them, and the last block is not
    stored.  Counted twice: by the launches' spans and by the downloads."""
    from paddle_tpu.serving import trace

    pool = pool_of(model, "paged")
    tracer = trace.Tracer(capacity=1024)
    with trace.tracing(tracer):
        rid = pool.submit(prompt_of(prompt_len), max_new)
        out = pool.run()[rid].tolist()
    assert out == ref.generate(weights, prompt_of(prompt_len), max_new,
                               SIZES)[0]
    spans = [e.meta for e in tracer.recorder.snapshot()
             if e.name == "tick.decode"]
    forwards = CFG["denoise_steps"] * blocks
    assert sum(m["live"] for m in spans) == forwards
    assert sum(m["stores_carried"] for m in spans) == blocks - 1
    assert all(m["store"] == 0 and m["rows"] == 8 * m["live"]
               for m in spans)
    assert sum(m["committed"] for m in spans) == max_new
    assert pool.block_stats() == {"forwards_denoise": forwards,
                                  "stores_carried": blocks - 1,
                                  "tokens_committed": max_new}
    assert pool.compile_counts()["block_step"] == 1


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_a_carried_store_writes_what_the_block_alone_writes(model, weights,
                                                            layout):
    """The pools after a request: at the positions of every block that a
    later block's first step stored, K and V are what a forward of that
    clean block alone, at its own index, writes (float32 here: the sums
    of the projections in another order, nothing like a bfloat16 step).
    The positions after them, the unstored last block's and the dead
    rows', are held to nothing."""
    prompt, max_new = prompt_of(9), 14
    tokens, _ = ref.generate(weights, prompt, max_new, SIZES)
    pool = pool_of(model, layout)
    rid = pool.submit(prompt, max_new)
    pool.step()
    (slot,) = pool._active
    table = np.asarray(pool._cache[0].table)[slot] if layout == "paged" \
        else None
    assert pool.run()[rid].tolist() == tokens
    params, bufs, cache = prefilled(pool, prompt)
    run = jax.jit(lambda ids, c: pool._session._run_model(params, bufs, ids,
                                                          c))
    clean = list(prompt) + tokens
    stored = (9 + max_new - 1) // 4 * 4          # the last block's start
    for start in range(8, stored, 4):
        _, cache = run(jnp.asarray([clean[start:start + 4]]), cache)

    def row(entry, which, at, tbl):
        x = np.asarray(getattr(entry, which))
        if tbl is None:
            return x[at]                               # [H, S, D]
        h, d = x.shape[1], x.shape[3]
        return x[tbl].transpose(1, 0, 2, 3).reshape(h, -1, d)

    alone_table = np.asarray(cache[0].table)[0] if layout == "paged" \
        else None
    assert stored - 8 >= 8
    for got, want in zip(pool._cache, cache):
        for which in ("k", "v"):
            a = row(got, which, slot, table)[:, 8:stored]
            b = row(want, which, 0, alone_table)[:, 8:stored]
            assert np.abs(b).max() > 1e-2
            assert np.abs(a - b).max() < 1e-5


def test_pool_interleaves_requests_of_every_alignment(model, weights):
    pool = pool_of(model, "paged")
    reqs = [(prompt_of(n, 1), k) for n, k in
            [(8, 8), (9, 7), (11, 10), (4, 3), (17, 9), (6, 1)]]
    rids = [pool.submit(p, k) for p, k in reqs]
    out = pool.run()
    for rid, (p, k) in zip(rids, reqs):
        assert out[rid].tolist() == ref.generate(weights, p, k, SIZES)[0]
    assert pool.compile_counts() == {"block_prefill": 2, "block_step": 1,
                                     "slot_insert": 1}
    stats = pool.block_stats()
    assert stats["tokens_committed"] == sum(k for _, k in reqs)
    assert 0 < stats["stores_carried"] < stats["forwards_denoise"]
    assert len(pool._free_blocks) == pool._num_blocks - 1


def test_eos_ends_a_request_at_that_token_in_position_order(model, weights):
    prompt = prompt_of(9)
    tokens, _ = ref.generate(weights, prompt, 12, SIZES)
    pool = pool_of(model, "dense", eos_id=tokens[5])
    rid = pool.submit(prompt, 12)
    got = pool.run()[rid].tolist()
    assert got == tokens[:tokens.index(tokens[5]) + 1]


@pytest.mark.parametrize("fill,steps,plan", [
    (4, 2, [2, 2]), (3, 2, [2, 1]), (1, 2, [1]), (2, 4, [1, 1]),
    (4, 4, [1, 1, 1, 1]), (4, 1, [4]), (0, 2, [])])
def test_commit_plan_is_the_references(fill, steps, plan):
    assert commit_plan(fill, steps) == plan == ref.commit_plan(fill, steps)


# -- the expert layer ---------------------------------------------------------

def per_token_loop(x, wr, wg, wu, wd, top_k):
    """Every token on its own: softmax over all experts, the top_k,
    renormalised, each chosen expert's gated SiLU feed-forward."""
    out = np.zeros_like(x)
    counts = np.zeros(wr.shape[1], int)
    for t, row in enumerate(x):
        z = row @ wr
        p = np.exp(z - z.max())
        p /= p.sum()
        top = np.argsort(-p, kind="stable")[:top_k]
        for e in top:
            counts[e] += 1
            a = row @ wg[e]
            out[t] += p[e] / p[top].sum() * (
                (a / (1 + np.exp(-a)) * (row @ wu[e])) @ wd[e])
    return out, counts


def uneven_case(rows=64):
    rng = np.random.default_rng(3)
    h, f, e = 32, 16, 8
    x = rng.normal(size=(rows, h)).astype(np.float32)
    x[:, 0] = 1.0                         # a constant channel
    x[:, 1] = np.where(np.arange(rows) % 2 == 0, 3.0, -3.0)
    wr = (0.05 * rng.normal(size=(h, e))).astype(np.float32)
    wr[0, 6] = wr[0, 7] = -50.0           # experts 6 and 7: never chosen
    wr[1, 0] = 20.0                       # expert 0: every even row
    mats = [(0.3 * rng.normal(size=s)).astype(np.float32)
            for s in [(e, h, f), (e, h, f), (e, f, h)]]
    return x, wr, mats


@pytest.fixture
def toy_route_line(monkeypatch):
    """The line between the two routes of the expert layer, brought down to
    these widths (8 experts of 32 x 16): 32 rows x 8 experts."""
    from paddle_tpu.nn.functional import moe
    monkeypatch.setattr(moe, "_EVERY_EXPERT_MACS", 32 * 8 * 32 * 16)


@pytest.mark.parametrize("rows", [64, 32])
def test_expert_layer_drops_nothing_under_uneven_routing(rows,
                                                         toy_route_line):
    """64 rows go through the grouped matmuls, 32 through every expert on
    every row: the choice is made from shapes (multiply-adds a matrix)."""
    x, wr, (wg, wu, wd) = uneven_case(rows)
    want, counts = per_token_loop(x, wr, wg, wu, wd, 2)
    assert counts[0] == len(x) // 2 and counts[6] == counts[7] == 0
    assert counts.sum() == 2 * len(x)
    # the scores are the caller's: here the one matrix
    got = np.asarray(jax.jit(
        lambda *a: F.sparse_experts(*a, top_k=2))(x, x @ wr, wg, wu, wd))
    # float32 sums in another order, values of order 1
    assert np.abs(got - want).max() < 1e-4
    # a [B, L, H] input keeps its shape (and the scores have its rows)
    x3 = x.reshape(4, rows // 4, -1)
    again = np.asarray(F.sparse_experts(x3, x3 @ wr, wg, wu, wd, top_k=2))
    assert np.abs(again.reshape(got.shape) - got).max() < 1e-6


def test_expert_layer_holding_a_share_adds_its_share_only(toy_route_line):
    # 64 rows: all 8 held and a share of 5 take the grouped route, a share
    # of 3 and the 10 rows below every expert on every row
    x, wr, (wg, wu, wd) = uneven_case()
    whole = np.asarray(F.sparse_experts(x, x @ wr, wg, wu, wd, top_k=2))
    parts = [np.asarray(F.sparse_experts(
        x, x @ wr, wg[a:b], wu[a:b], wd[a:b], top_k=2, first_expert=a))
        for a, b in [(0, 3), (3, 8)]]
    assert np.abs(parts[0] + parts[1] - whole).max() < 1e-5
    few = np.asarray(F.sparse_experts(x[:10], x[:10] @ wr, wg[:3], wu[:3],
                                      wd[:3], top_k=2))
    assert np.abs(few - parts[0][:10]).max() < 1e-5
    assert np.abs(parts[0]).max() > 0.1 and np.abs(parts[1]).max() > 0.1
    layer = pt.nn.SparseExperts(32, 16, 8, 2, held=(3, 5))
    assert layer.w_gate.shape == [5, 32, 16] and layer.router.shape == [32,
                                                                        8]
    with pytest.raises(InvalidArgumentError, match="held"):
        pt.nn.SparseExperts(32, 16, 8, 2, held=(6, 3))


# -- the kernel ---------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16",
                                                           2e-2)])
def test_grouped_kernel_matches_the_composition(dtype, tol):
    """Interpret mode: 8 query heads on 2 K/V heads, Lq 4, every row of a
    batch row with a last visible key of its own.  float32: sums in
    another order; bfloat16: the composition rounds its probabilities to
    bfloat16 before the last matmul and the kernel does not, 2**-8 of
    values of order 1."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")

    rng = np.random.default_rng(5)
    b, hq, hkv, lq, d, bs, mb = 3, 8, 2, 4, 64, 8, 5
    nb = 1 + b * mb
    q = jnp.asarray(rng.normal(size=(b, hq, lq, d)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), dtype)
    table = jnp.asarray(1 + rng.permutation(b * mb).reshape(b, mb),
                        jnp.int32)
    q_pos = jnp.asarray([[3, 7, 7, 5], [39, 39, 39, 39], [16, 17, 18, 19]],
                        jnp.int32)
    want = fa.paged_decode_attention(q, k_pool, v_pool, table, q_pos=q_pos,
                                     route="composition")
    got = fa.paged_decode_attention(q, k_pool, v_pool, table, q_pos=q_pos,
                                    route="pallas")
    assert got.shape == (b, hq, lq, d) and got.dtype == q.dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol
    # the composition itself against a per-head loop with repeated K/V
    k = k_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, hkv, mb * bs, d)
    v = v_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, hkv, mb * bs, d)
    rep = lambda a: jnp.repeat(a, hq // hkv, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), rep(k)) \
        / np.sqrt(d)
    allow = jnp.arange(mb * bs)[None, None, None, :] \
        <= q_pos[:, None, :, None]
    plain = jnp.einsum("bhqk,bhkd->bhqd",
                       jax.nn.softmax(jnp.where(allow, s, -1e30), -1),
                       rep(v))
    assert np.abs(np.asarray(want, np.float32)
                  - np.asarray(plain)).max() < tol


def test_one_head_a_head_pool_goes_through_the_same_call_unchanged():
    """The shapes every other model uses (query heads = pool heads): the
    kernel's call has no group and its body is the one it was."""
    from paddle_tpu.ops import pallas_decode

    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(2, 4, 1, 64)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(9, 4, 8, 64)), jnp.float32)
    table = jnp.asarray(1 + np.arange(8).reshape(2, 4), jnp.int32)
    q_pos = jnp.asarray([[13], [30]], jnp.int32)
    text = str(jax.make_jaxpr(lambda *a: pallas_decode
               .paged_decode_attention_kernel(*a, 0.125, interpret=True))(
        q, pool, pool, table, q_pos))
    assert "rem" not in text       # the grouped body's ``row % lq``
    with pytest.raises(InvalidArgumentError, match="whole multiple"):
        pallas_decode.paged_decode_attention_kernel(
            q[:, :3], pool, pool, table, q_pos, 0.125, interpret=True)


# -- the engine ---------------------------------------------------------------

def test_engine_serves_the_plain_loops_tokens_over_http(model, weights):
    from harness.client import StreamClient
    from paddle_tpu.serving import (ServingEngine, ServingHTTPFrontend,
                                    trace)

    engine = ServingEngine(model, max_len=64, slots=2, cache_layout="paged",
                           block_size=8, buckets=[16, 32],
                           cache_dtype="float32")
    assert isinstance(engine._pool, BlockDiffusionPool)
    front = ServingHTTPFrontend(engine)
    engine.start()
    front.start()
    tracer = trace.Tracer(capacity=4096)
    try:
        with trace.tracing(tracer):
            client = StreamClient(*front.address)
            recs = []
            for i, (n, k) in enumerate([(9, 7), (12, 8), (5, 5)]):
                rec = {"index": i, "prompt": prompt_of(n, 2).tolist(),
                       "k": k}
                recs.append(rec)
                client.send(rec, rec["prompt"], k)
            deadline = time.time() + 300
            while any(r["done"] is None for r in recs) \
                    and time.time() < deadline:
                client.poll(0.2)
        for r in recs:
            tokens, steps = ref.generate(weights, r["prompt"], r["k"],
                                         SIZES)
            assert r["status"] == "ok" and r["tokens"] == tokens
            assert r["final"]["commit_steps"] == steps
            assert r["final"]["new_tokens"] == r["k"]
        import urllib.request
        text = urllib.request.urlopen(
            "http://%s:%d/metrics" % front.address).read().decode()
        counters = {l.split()[0]: float(l.split()[1])
                    for l in text.splitlines()
                    if l.startswith("serving_block_")}
        assert counters["serving_block_tokens_committed_total"] == 20
        # 7, 8 and 5 tokens after prompts that end 1, 0 and 1 into a
        # block: two blocks each, the second never stored
        assert counters["serving_block_stores_carried_total"] == 3
        assert counters["serving_block_forwards_denoise_total"] == 12
    finally:
        front.shutdown()
        engine.shutdown(drain=False)
    spans = [e.meta for e in tracer.recorder.snapshot()
             if e.name == "tick.decode"]
    assert spans and all(
        {"rows", "committed", "store", "stores_carried", "live"}
        <= set(m) for m in spans)
    assert sum(m["committed"] for m in spans) == 20
    assert sum(m["stores_carried"] for m in spans) == 3
    assert all(m["rows"] == 8 * m["live"] and m["store"] == 0
               for m in spans)


def test_a_plain_models_terminal_line_has_no_commit_steps():
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    lm = TransformerLM(vocab_size=64, hidden_size=32, num_layers=1,
                       num_heads=2, intermediate_size=64, max_position=32,
                       dropout=0.0)
    lm.eval()
    engine = ServingEngine(lm, max_len=32, slots=1)
    stream = engine.submit(np.arange(5), 3)
    assert len(list(stream)) == 3
    assert stream.status.commit_steps is None


@pytest.mark.parametrize("kwargs,error,match", [
    (dict(prefix_sharing=True, prefill_chunk_tokens=8,
          cache_layout="paged", block_size=8), InvalidArgumentError,
     "chunked prefill"),
    (dict(prefix_sharing=True), InvalidArgumentError, "prefix sharing"),
    (dict(spill_tier="disk", spill_dir="/tmp/x"), InvalidArgumentError,
     "spill"),
    (dict(prefill_only=True), InvalidArgumentError, "prefill-only"),
    (dict(cache_dtype="int8"), InvalidArgumentError, "int8"),
    (dict(temperature=0.7), InvalidArgumentError, "argmax"),
    (dict(cache_layout="paged", block_size=6), InvalidArgumentError,
     "block_length"),
    (dict(collective_quant="int8"), InvalidArgumentError, "collectives"),
])
def test_what_the_block_pool_cannot_do_is_refused_by_name(model, kwargs,
                                                          error, match):
    with pytest.raises(error, match=match):
        BlockDiffusionPool(model, 64, slots=2, **kwargs)


def test_block_pool_refuses_other_models_lengths_and_requests(model):
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    lm = TransformerLM(vocab_size=64, hidden_size=32, num_layers=1,
                       num_heads=2, intermediate_size=64, max_position=32,
                       dropout=0.0)
    with pytest.raises(InvalidArgumentError, match="block_diffusion"):
        BlockDiffusionPool(lm, 32)
    with pytest.raises(InvalidArgumentError, match="multiple"):
        BlockDiffusionPool(model, 62)
    with pytest.raises(InvalidArgumentError, match="speculative"):
        ServingEngine(model, max_len=64, draft_model=lm)
    pool = pool_of(model, "dense")
    with pytest.raises(InvalidArgumentError, match="temperature"):
        pool.submit(prompt_of(5), 4, temperature=0.5)
    rid = pool.submit(prompt_of(5), 4)
    pool.step()
    assert pool.can_preempt(rid) is False
    with pytest.raises(PreconditionNotMetError, match="preempt"):
        pool.preempt(rid)
    assert pool.cancel(rid) in ("active", "queued")
    assert not pool._blocks and len(pool._free) == pool.slots


def test_model_refuses_what_it_cannot_be():
    base = bw.model_kwargs(CFG)
    with pytest.raises(InvalidArgumentError, match="mask_token_id"):
        BlockDiffusionMoELM(**dict(base, mask_token_id=512))
    with pytest.raises(InvalidArgumentError, match="denoise_steps"):
        BlockDiffusionMoELM(**dict(base, denoise_steps=5))
    with pytest.raises(InvalidArgumentError, match="whole multiple"):
        BlockDiffusionMoELM(**dict(base, num_kv_heads=3))
    m = BlockDiffusionMoELM(**dict(base, num_layers=1, dtype="bfloat16"))
    assert {str(p.value.dtype) for p in m.parameters()} == {"bfloat16"}
    with pytest.raises(InvalidArgumentError, match="int8"):
        m.gen_decode_cache(1, 16, "int8")
    cache = m.gen_decode_cache(2, 16, "bfloat16", layout="paged",
                               block_size=8, num_blocks=5)
    assert cache[0].k.shape == (5, 2, 8, 16)      # K/V heads, not query's
    assert json.dumps(sorted(n for n, _ in m.named_parameters()))\
        .count("moe.w_") == 3
