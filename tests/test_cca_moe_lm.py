"""A decoder of compressed convolutional attention and top-1 routed experts
under a router that carries its state through the depth
(``models.CCAMoELM``), served through a cache list of TWO entries a layer.

At small widths on the CPU, float32 (3 layers of width 64, 4 query heads on
2 K/V heads of 16, taps (2, 2), 8 experts one a token, router width 16, 256
rows of vocabulary; the benchmark's seeded weights of ``toy-cca.json``):

1. the model's forward and its cached forward against the plain reference's
   logits (``benchmark/harness/cca_reference.py``); both controls move them;
2. the router as a layer: against the reference's, its carry, the gate of
   one expert a token, the shares that add up to the layer, the matrix
   router traced as it was;
3. ``GenerationPool`` and ``ServingEngine`` over HTTP: every served token the
   reference's best; the padded bucket, the free slot, two requests,
   preempt and resume over both entries of every layer;
4. what the hooks say, counting entries and not layers; no retrace;
5. what the cache cannot carry is refused by a typed error that names the
   recurrent entries.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool, SpeculativePool
from paddle_tpu.jit import DecodeSession
from paddle_tpu.jit.cache import (ComposedLayout, entry_layout, get_layout,
                                  layout_of)
from paddle_tpu.models import CCAMoELM, TransformerLM
from paddle_tpu.nn import functional as F

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import cca_reference as ref  # noqa: E402
from harness import cca_weights as cw  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "toy-cca.json")) as _f:
    CFG = json.load(_f)
SEED = 11
LAYERS = 3
KV_BYTES = 2 * 2 * 16 * 4                 # a position a layer: K and V heads
STATE_BYTES = (96 + 96 + 16) * 4          # a slot a layer: u, c0, v_next


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = CCAMoELM(**cw.model_kwargs(CFG))
    m.eval()
    cw.load_into(m, CFG, SEED)
    return m


@pytest.fixture(scope="module")
def weights():
    return cw.make_weights(CFG, SEED)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _pool(model, **kw):
    kw.setdefault("cache_layout", "paged")
    kw.setdefault("block_size", 8)
    kw.setdefault("slots", 2)
    kw.setdefault("buckets", [16, 32])
    kw.setdefault("cache_dtype", "float32")
    return GenerationPool(model, max_len=64, **kw)


def _serve(model, prompts, new=8, **kw):
    pool = _pool(model, **kw)
    for i, ids in enumerate(prompts):
        pool.submit(ids, new, request_id=i)
    return pool.run(), pool


def _assert_best(weights, prompt, toks):
    """Every served token the reference's best at its position: 1e-4 is two
    orders of float32 summation at logits of order 3."""
    seq = np.concatenate([prompt, toks[:-1]])
    logits = ref.forward_logits(weights, seq, cw.sizes(CFG))
    rows = logits[len(prompt) - 1:]
    gap = jnp.max(rows, -1) - rows[jnp.arange(len(toks)), jnp.asarray(toks)]
    assert float(jnp.max(gap)) <= 1e-4
    assert len(set(toks)) > 2, "a model that repeats one token"


# -- 1. against the reference ---------------------------------------------------

def test_the_model_is_what_the_configuration_says(model):
    layer = model.layers[1]
    assert layer.moe.held == (0, 8) and layer.moe.top_k == 1
    assert layer.moe.renormalise is False and layer.moe.shared is None
    assert layer.moe.w_gate.shape == [8, 64, 32]
    assert isinstance(layer.moe.router, pt.nn.DepthMLPRouter)
    assert model.layers[0].moe.router.first \
        and not model.layers[1].moe.router.first
    assert layer.self_attn.rotary_dim == 8
    names = {n for n, _ in model.named_parameters()}
    assert "layers.1.moe.router.gamma" in names
    assert "layers.0.moe.router.gamma" not in names
    assert not any(n.startswith("lm_head") for n in names)     # tied
    # at the published widths: 207,567,106 parameters a layer, 8.30 B in 40
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "zaya1-8b.json")))
    big = jax.eval_shape(lambda: [p.value for p in CCAMoELM(
        **dict(cw.model_kwargs(cfg), num_layers=2)).layers[1].parameters()])
    assert sum(int(np.prod(p.shape)) for p in big) == 207567106


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cached_forward_agrees_with_the_reference_on_logits(model, weights,
                                                            layout):
    ids = _prompts([40], seed=3)[0]
    want = ref.forward_logits(weights, ids, cw.sizes(CFG))
    full = model(pt.to_tensor(jnp.asarray(ids[None]))).value[0]
    # float32 on both sides: two orders of summation at logits of order 3
    assert float(jnp.max(jnp.abs(full - want))) < 1e-4
    assert float(jnp.max(jnp.abs(want))) > 1.0
    cache = model.gen_decode_cache(1, 48, "float32", layout=layout,
                                   block_size=8)
    assert len(cache) == 2 * LAYERS
    # a prompt, its last logits alone; a chunk that starts mid-way; steps
    lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, :24])), cache=cache,
                      last=23)
    got = [lg.value[0, 0]]
    lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, 24:29])),
                      cache=cache)
    got += list(lg.value[0])
    for t in range(29, 40):
        lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, t:t + 1])),
                          cache=cache)
        got.append(lg.value[0, 0])
    assert float(jnp.max(jnp.abs(jnp.stack(got) - want[23:]))) < 1e-4


def test_the_reference_controls_move_the_logits(weights):
    ids = _prompts([32], seed=4)[0]
    sound = ref.forward_logits(weights, ids, cw.sizes(CFG))
    for mode in ("fp8", "no_mix"):
        moved = ref.forward_logits(weights, ids, cw.sizes(CFG), mode)
        assert float(jnp.max(jnp.abs(moved - sound))) > 0.05, mode


# -- 2. the router as a layer ------------------------------------------------------

def test_the_router_agrees_with_the_reference_and_carries_its_state(model,
                                                                    weights):
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.normal(size=(2, 5, 64)), jnp.float32)
    first, later = (model.layers[i].moe.router for i in (0, 1))
    s0, r0 = first(pt.to_tensor(m))
    want_p, want_r = ref.router(m[0], None, weights["layers"][0],
                                cw.sizes(CFG), "float32")
    assert s0.shape == (2, 5, 8) and r0.shape == (2, 5, 16)
    assert s0.dtype == r0.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(jax.nn.softmax(s0[0]) - want_p))) < 1e-5
    assert float(jnp.max(jnp.abs(r0[0] - want_r))) < 1e-5
    s1, r1 = later(pt.to_tensor(m), r0)
    want_p, want_r = ref.router(m[0], r0[0], weights["layers"][1],
                                cw.sizes(CFG), "float32")
    assert float(jnp.max(jnp.abs(jax.nn.softmax(s1[0]) - want_p))) < 1e-5
    assert float(jnp.max(jnp.abs(r1[0] - want_r))) < 1e-5
    # the state of the layer before matters, and each layer says what it
    # takes
    s1b, _ = later(pt.to_tensor(m), 2.0 * r0)
    assert float(jnp.max(jnp.abs(s1b - s1))) > 0.1
    with pytest.raises(InvalidArgumentError, match="first layer takes no"):
        first(pt.to_tensor(m), r0)
    with pytest.raises(InvalidArgumentError, match="later layer takes the"):
        later(pt.to_tensor(m))


def test_one_expert_a_token_is_gated_by_its_probability():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(6, 8)),
                         jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = F.route_top_k(logits, 1, renormalise=False)
    assert experts[:, 0].tolist() == jnp.argmax(probs, -1).tolist()
    assert bool(jnp.allclose(gates[:, 0], jnp.max(probs, -1)))
    assert float(jnp.max(gates)) < 1.0
    # renormalised, the rule every other model uses, the gate says nothing
    gates, same = F.route_top_k(logits, 1)
    assert bool(jnp.all(gates == 1.0)) and bool(jnp.all(same == experts))
    # two a token, as they stand: the two largest probabilities
    gates, _ = F.route_top_k(logits, 2, renormalise=False)
    assert bool(jnp.allclose(gates, jax.lax.top_k(probs, 2)[0]))
    # the sigmoid rule as it stands: the scores times the scale
    gates, experts = F.route_top_k(logits, 2, "sigmoid", scale=2.5,
                                   renormalise=False)
    assert bool(jnp.allclose(
        gates, 2.5 * jax.lax.top_k(jax.nn.sigmoid(logits), 2)[0]))


def test_the_shares_under_the_mlp_router_add_up_to_the_layer(model):
    whole = model.layers[1].moe
    rng = np.random.default_rng(2)
    m = pt.to_tensor(jnp.asarray(rng.normal(size=(1, 24, 64)), jnp.float32))
    _, r_prev = model.layers[0].moe.router(m)
    want, r = whole(m, r_prev)
    parts = []
    for first in (0, 4):
        share = pt.nn.SparseExperts(64, 32, 8, 1, held=(first, 4),
                                    renormalise=False, router=whole.router)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name)._replace_value(
                getattr(whole, name).value[first:first + 4])
        part, r_share = share(m, r_prev)
        assert bool(jnp.all(r_share == r))      # every holder agrees
        parts.append(part.value)
    assert float(jnp.max(jnp.abs(parts[0] + parts[1] - want.value))) < 1e-6
    # each share adds something, and a row's output lies in one share
    assert all(float(jnp.max(jnp.abs(p))) > 0.01 for p in parts)
    rows = [jnp.any(p != 0, axis=-1) for p in parts]
    assert not bool(jnp.any(rows[0] & rows[1]))


def test_the_matrix_router_traces_what_it_traced():
    """The default router is the layer's own matrix: scores in float32 from
    the rows, then the experts; the jaxpr is the one written out here."""
    pt.seed(0)
    layer = pt.nn.SparseExperts(32, 16, 8, 2)
    assert layer.router.shape == [32, 8] and layer.renormalise

    def by_hand(x):
        xt = x.reshape(-1, 32)
        scores = jnp.matmul(xt, layer.router.value,
                            preferred_element_type=jnp.float32)
        return F.sparse_experts(xt, scores, layer.w_gate.value,
                                layer.w_up.value, layer.w_down.value,
                                top_k=2).reshape(x.shape)

    x = jnp.ones((2, 3, 32))
    got = jax.make_jaxpr(lambda x: layer(pt.to_tensor(x)).value)(x)
    assert str(got) == str(jax.make_jaxpr(by_hand)(x))
    prims = [e.primitive.name for e in got.jaxpr.eqns]
    assert prims[:2] == ["reshape", "dot_general"] and "erf" not in prims


# -- 3. through the pool and the engine -----------------------------------------

def test_pool_prefill_then_decode_agrees_with_the_reference(model, weights):
    prompts = _prompts([13, 27, 9])
    got, pool = _serve(model, prompts, new=10)
    for i, prompt in enumerate(prompts):
        _assert_best(weights, prompt, [int(t) for t in got[i]])
    assert pool.compile_counts() == {"prefill": 2, "decode": 0,
                                     "pool_decode": 1, "slot_insert": 1}
    dense, _ = _serve(model, prompts, new=10, cache_layout="dense")
    for i in got:
        np.testing.assert_array_equal(dense[i], got[i])


@pytest.mark.parametrize("true_len", [1, 2, 13])
def test_a_padded_bucket_leaves_the_state_of_the_true_length(model,
                                                             true_len):
    ids = _prompts([16], seed=5)[0]
    sess = DecodeSession(model, max_len=64, buckets=[16],
                         cache_layout="paged", block_size=8,
                         cache_dtype="float32")
    padded, _, _ = sess.prefill(ids[None, :true_len])
    exact = model.gen_decode_cache(1, 64, "float32", layout="paged",
                                   block_size=8)
    _, exact = model(pt.to_tensor(ids[None, :true_len]), cache=exact)
    assert len(padded) == 2 * LAYERS
    for got, want in zip(padded, exact):
        if entry_layout(got).recurrent:
            for f in ("u", "c0", "v_next"):
                assert float(jnp.max(jnp.abs(
                    getattr(got, f) - getattr(want, f)))) <= 1e-5, f
            assert int(got.limit) == 64
        assert int(got.index) == true_len


def test_a_free_slots_state_and_kv_come_through_the_step_untouched(model):
    pool = _pool(model, slots=3)
    params, bufs = pool._session._state_vals()
    rng = np.random.default_rng(0)
    rnd = lambda a: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
    table = jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0], [3, 4, 0, 0, 0, 0, 0, 0],
                         [5, 6, 0, 0, 0, 0, 0, 0]], jnp.int32)
    idx = jnp.asarray([5, 7, 9], jnp.int32)
    cache = [c._replace(u=rnd(c.u), c0=rnd(c.c0), v_next=rnd(c.v_next),
                        index=idx) if lay.recurrent else
             c._replace(k=rnd(c.k), v=rnd(c.v), table=table, index=idx)
             for lay, c in zip(pool._layout.layouts(pool._cache),
                               pool._cache)]
    n = pool.slots
    active = np.asarray([True, False, True])
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    new, tok, _ = pool._pool_decode(
        params, bufs, cache, jnp.asarray([3, 4, 5], jnp.int32), active,
        samp, np.zeros(n, np.uint32), np.zeros(n, np.int32))
    for lay, old, c in zip(pool._layout.layouts(cache), cache, new):
        assert c.index.tolist() == [6, 7, 10]
        if lay.recurrent:
            for f in ("u", "c0", "v_next"):
                assert bool(jnp.all(getattr(c, f)[1] == getattr(old, f)[1]))
                assert not bool(jnp.any(getattr(c, f)[0]
                                        == getattr(old, f)[0]))
            assert c.limit.shape == () and int(c.limit) == 64
        else:
            assert bool(jnp.all(c.table == table))
            for b in (3, 4):        # the free slot's own blocks
                assert bool(jnp.all(c.k[b] == old.k[b]))
                assert bool(jnp.all(c.v[b] == old.v[b]))
            # a live slot wrote its position: block 1, row 5
            assert not bool(jnp.all(c.k[1, :, 5] == old.k[1, :, 5]))
    assert int(tok[1]) == 0


def test_two_requests_do_not_touch_each_others_state(model):
    a, b = _prompts([21, 6], seed=4)
    alone, _ = _serve(model, [a], new=12)
    both, _ = _serve(model, [a, b], new=12)
    np.testing.assert_array_equal(both[0], alone[0])
    other, _ = _serve(model, [b], new=12)
    np.testing.assert_array_equal(both[1], other[0])


def test_preempt_and_resume_carry_both_entries_of_every_layer(model):
    prompts = _prompts([5, 19, 11], seed=2)
    want, ref_pool = _serve(model, prompts, new=12)
    pool = _pool(model)
    for i, ids in enumerate(prompts):
        pool.submit(ids, 12, request_id=i)
    for _ in range(4):
        pool.step()
    victim = next(st.rid for st in pool._active.values())
    assert pool.can_preempt(victim)
    info = pool.preempt(victim)
    assert info["state_bytes"] == LAYERS * STATE_BYTES
    assert info["blocks_spilled"] >= 1
    assert info["spill_bytes"] == info["state_bytes"] \
        + info["blocks_spilled"] * 8 * LAYERS * KV_BYTES
    got = pool.run()
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    assert pool.compile_counts() == ref_pool.compile_counts()
    stats = pool.spill_stats()
    assert stats["preempts_total"] == 1 and stats["resumes_total"] == 1
    cs = pool.cache_stats()
    assert cs["free_blocks"] == cs["num_blocks"] - 1


def test_served_over_http_through_the_engine(model, weights):
    import urllib.request

    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    prompt = _prompts([14], seed=9)[0]
    engine = ServingEngine(model, max_len=64, slots=2, buckets=[16, 32],
                           cache_layout="paged", block_size=8,
                           cache_dtype="float32")
    front = ServingHTTPFrontend(engine)
    engine.start()
    front.start()
    try:
        host, port = front.address
        req = urllib.request.Request(
            "http://%s:%d/generate" % (host, port),
            data=json.dumps({"prompt": prompt.tolist(),
                             "max_new_tokens": 7}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            lines = [json.loads(l) for l in resp.read().splitlines() if l]
        toks = [l["token"] for l in lines if "token" in l]
        assert len(toks) == 7
        _assert_best(weights, prompt, toks)
        text = engine.metrics.render_prometheus().replace(".0\n", "\n")
        assert "serving_state_bytes_per_slot %d\n" % (LAYERS * STATE_BYTES) \
            in text
        assert 'serving_cache_entries{layout="recurrent"} 3\n' in text
        assert 'serving_cache_entries{layout="paged"} 3\n' in text
        assert "serving_kv_free_blocks" in text
    finally:
        front.shutdown()
        engine.shutdown(drain=False)


# -- 4. what the hooks say ----------------------------------------------------------

def test_the_layout_is_composed_over_two_entries_a_layer(model):
    cache = model.gen_decode_cache(2, 64, "float32", layout="paged",
                                   block_size=8, per_slot=True)
    assert [entry_layout(c).name for c in cache] \
        == ["paged", "recurrent"] * LAYERS
    layout = layout_of(cache)
    assert isinstance(layout, ComposedLayout)
    assert layout.name == "paged+recurrent"
    assert (layout.paged, layout.positional, layout.spillable,
            layout.transferable, layout.recurrent) \
        == (True, False, True, False, True)
    # entries, not layers: three layers own six
    assert "3 of the 6 cache entries are states of constant size " \
        "(entries 1, 3, 5)" in layout.recurrent_entries()
    assert get_layout("recurrent").recurrent_entries() \
        == "every cache entry is a state of constant size"
    assert len(layout.entries(cache, "paged")) == LAYERS
    assert len(layout.entries(cache, "recurrent")) == LAYERS
    assert layout.bytes_per_slot_by_kind(cache, 2, 64) == {
        "paged": (LAYERS, LAYERS * 64 * KV_BYTES),
        "recurrent": (LAYERS, LAYERS * STATE_BYTES)}
    assert get_layout("recurrent").state_fields(cache[1]) \
        == ("u", "c0", "v_next")
    dense = layout_of(model.gen_decode_cache(1, 64, layout="dense"))
    assert dense.name == "dense+recurrent" and not dense.spillable


def test_spans_and_stats_count_entries_and_nothing_retraces(model):
    from paddle_tpu.serving import trace as engine_trace

    tracer = engine_trace.Tracer(capacity=4096)
    engine_trace.install(tracer)
    try:
        pool = _pool(model, slots=3)
        assert pool._depth == 1
        prompts = _prompts([13, 27, 4, 9, 30, 16], seed=6)
        for i, ids in enumerate(prompts):
            pool.submit(ids, 3 + 2 * i, request_id=i)
        pool.run()
    finally:
        engine_trace.uninstall()
    assert pool.compile_counts() == {"prefill": 2, "decode": 0,
                                     "pool_decode": 1, "slot_insert": 1}
    decodes = [e.meta for e in tracer.recorder.snapshot()
               if e.name == "tick.decode"]
    assert decodes and all(
        m["state_bytes"] == m["live"] * LAYERS * STATE_BYTES
        and m["state_entries"] == LAYERS and m["kv_entries"] == LAYERS
        and m["table_blocks"] == 3 * 8
        and 1 <= m["live_blocks"] <= m["live"] * 8
        and "state_layers" not in m and "kv_layers" not in m
        for m in decodes)
    assert any(m["ahead"] == 1 for m in decodes)
    stats = pool.cache_stats()
    assert stats["cache_layout"] == pool.cache_layout == "paged+recurrent"
    assert stats["cache_entries"] == {"paged": LAYERS, "recurrent": LAYERS}
    assert "cache_layers" not in stats
    assert stats["bytes_per_slot"] == {"paged": LAYERS * 64 * KV_BYTES,
                                       "recurrent": LAYERS * STATE_BYTES}
    # the K/V figures run over the K/V entries, not over the list's length
    state_total = 3 * LAYERS * STATE_BYTES
    assert stats["dense_equiv_bytes"] == 3 * LAYERS * 64 * KV_BYTES
    assert stats["pool_bytes"] == state_total \
        + stats["num_blocks"] * 8 * LAYERS * KV_BYTES
    assert stats["reachable_bytes"] == state_total       # nothing mapped
    fp = pool.config_fingerprint()
    assert fp["cache_layout"] == "paged+recurrent" and fp["block_size"] == 8
    assert fp["state_shapes"] == [[96], [96], [16]]


# -- 5. refusals ------------------------------------------------------------------

REFUSED = {
    "prefix_sharing": (dict(prefix_sharing=True, prefill_chunk_tokens=8),
                       "prefill_chunk_tokens.*3 of the 6 cache entries"),
    "prefix_sharing_alone": (dict(prefix_sharing=True),
                             "prefix_sharing.*paged\\+recurrent.*3 of the 6"),
    "chunked_prefill": (dict(prefill_chunk_tokens=8),
                        "prefill_chunk_tokens.*paged\\+recurrent.*3 of the 6"),
    "disk_spill": (dict(spill_tier="disk", spill_dir="unused"),
                   "spill_tier='disk'.*paged\\+recurrent.*3 of the 6"),
    "ptkv_hand_off": (dict(prefill_only=True, spill_tier="disk",
                           spill_dir="unused"),
                      "spill_tier='disk'.*paged\\+recurrent.*3 of the 6"),
    "recurrent_layout": (dict(cache_layout="recurrent"),
                         "CCAMoELM supports cache_layouts"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_the_cache_cannot_carry_is_refused_by_name(model, feature,
                                                        tmp_path):
    kw, match = REFUSED[feature]
    if "spill_dir" in kw:
        kw = dict(kw, spill_dir=str(tmp_path))
    with pytest.raises(InvalidArgumentError, match=match):
        _pool(model, **kw)


def test_speculative_pools_and_the_model_name_what_they_refuse(model):
    draft = TransformerLM(vocab_size=256, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=64,
                          max_position=64, causal=True, dropout=0.0)
    with pytest.raises(InvalidArgumentError,
                       match="speculative.*paged\\+recurrent.*3 of the 6"):
        SpeculativePool(model, draft, max_len=64, cache_layout="paged",
                        block_size=8, buckets=[16])
    with pytest.raises(InvalidArgumentError, match="K/V entries are kept"):
        model.gen_decode_cache(1, 64, layout="recurrent")
