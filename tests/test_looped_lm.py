"""A decoder whose stack runs several times on shared weights
(``models.LoopedLM``), served through cache entries of several K/V planes.

At small widths on the CPU, float32 (2 layers of width 64 run 3 times, 4
heads of 16, MLP of 128, 256 rows of vocabulary; the benchmark's seeded
weights of ``toy-looped.json``):

1. the model's forward and its cached forward (dense and paged planes)
   against the plain reference's logits
   (``benchmark/harness/looped_reference.py``); one pass is the plain stack;
   the three controls move the logits;
2. two passes read DIFFERENT planes; the exit rule against a hand-built
   distribution;
3. the passes are ONE loop in the compiled decode step, the layers' bodies
   in it once;
4. ``GenerationPool`` and ``ServingEngine`` over HTTP: every served token the
   reference's best; preempt and resume, chunked prefill and prefix sharing
   carry the planes with their blocks; what the hooks, stats and gauges say;
5. what the planes cannot carry is refused by a typed error that names them.
"""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool
from paddle_tpu.jit import DecodeSession
from paddle_tpu.jit.cache import get_layout, layout_of
from paddle_tpu.models import LoopedLM
from paddle_tpu.models.looped_lm import exit_pass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import looped_reference as ref  # noqa: E402
from harness import looped_weights as lw  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "toy-looped.json")) as _f:
    CFG = json.load(_f)
SEED = 11
LAYERS, PASSES, HEADS, HEAD_DIM = 2, 3, 4, 16
PLANE_BYTES = 2 * HEADS * HEAD_DIM * 4      # a position a layer a plane


def _model(**changed):
    pt.seed(0)
    cfg = dict(CFG, **changed)
    m = LoopedLM(**lw.model_kwargs(cfg))
    m.eval()
    lw.load_into(m, cfg, SEED)
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def weights():
    return lw.make_weights(CFG, SEED)


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


def _assert_best(weights, prompt, toks, sizes=None):
    """Every served token the reference's best at its position: 1e-4 is two
    orders of float32 summation at logits of order 3."""
    seq = np.concatenate([prompt, toks[:-1]])
    logits = ref.forward_logits(weights, seq, sizes or lw.sizes(CFG))
    rows = logits[len(prompt) - 1:]
    gap = jnp.max(rows, -1) - rows[jnp.arange(len(toks)), jnp.asarray(toks)]
    assert float(jnp.max(gap)) <= 1e-4
    assert len(set(toks)) > 2, "a model that repeats one token"


def _cached_logits(model, ids, layout):
    """A prompt (its last logits alone), a chunk that starts mid-way, then
    steps: the logits of positions 23 on."""
    cache = model.gen_decode_cache(1, 48, "float32", layout=layout,
                                   block_size=8)
    lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, :24])), cache=cache,
                      last=23)
    got = [lg.value[0, 0]]
    lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, 24:29])),
                      cache=cache)
    got += list(lg.value[0])
    for t in range(29, len(ids)):
        lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, t:t + 1])),
                          cache=cache)
        got.append(lg.value[0, 0])
    return jnp.stack(got), cache


# -- 1. against the reference -------------------------------------------------

def test_the_model_is_what_the_configuration_says(model):
    assert (model.total_ut_steps, model.early_exit_threshold,
            model.cache_planes) == (3, 1.0, 3)
    names = {n for n, _ in model.named_parameters()}
    assert {"lm_head.weight", "early_exit_gate.weight",
            "early_exit_gate.bias", "final_norm.weight",
            "layers.1.attn_out_norm.weight",
            "layers.1.mlp_out_norm.weight"} <= names
    assert not any("bias" in n for n in names - {"early_exit_gate.bias"})
    assert model.layers[0].self_attn.q_norm is None
    # at the published widths: 51,388,416 parameters a layer; with the
    # embedding, the head, the final norm and the gate 2,667,974,657 in 48
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "ouro-2p6b.json")))
    big = jax.eval_shape(lambda: {
        n: p.value for n, p in LoopedLM(**dict(
            lw.model_kwargs(cfg), num_layers=1)).named_parameters()})
    count = lambda keep: sum(int(np.prod(p.shape))
                             for n, p in big.items() if keep(n))
    layer = count(lambda n: n.startswith("layers."))
    assert layer == 51388416
    assert 48 * layer + count(lambda n: not n.startswith("layers.")) \
        == 2667974657
    kw = lw.model_kwargs(cfg)
    assert (kw["total_ut_steps"], kw["early_exit_threshold"]) == (4, 1.0)


@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_full_forward_agrees_with_the_reference(weights, threshold):
    m = _model(early_exit_threshold=threshold)
    sizes = dict(lw.sizes(CFG), threshold=threshold)
    ids = _prompts([40], seed=3)[0]
    want = ref.forward_logits(weights, ids, sizes)
    full = m(pt.to_tensor(jnp.asarray(ids[None]))).value[0]
    # float32 on both sides: two orders of summation at logits of order 3
    assert float(jnp.max(jnp.abs(full - want))) < 1e-4
    assert float(jnp.max(jnp.abs(want))) > 1.0
    if threshold == 0.5:
        # the positions leave at different passes: the select is exercised
        last = ref.forward_logits(weights, ids, lw.sizes(CFG))
        moved = jnp.max(jnp.abs(want - last), axis=-1) > 1e-2
        assert 0 < int(moved.sum()) < len(ids)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cached_forward_agrees_with_the_reference_on_logits(model, weights,
                                                            layout):
    ids = _prompts([40], seed=3)[0]
    want = ref.forward_logits(weights, ids, lw.sizes(CFG))
    got, cache = _cached_logits(model, ids, layout)
    assert float(jnp.max(jnp.abs(got - want[23:]))) < 1e-4
    assert len(cache) == LAYERS and int(cache[0].index) == 40
    assert cache[0].k.shape[1] == PASSES * HEADS


def test_one_pass_is_the_plain_stack(weights):
    """``total_ut_steps = 1``: the gate is not read, the one pass carries
    all the mass, and the model is a plain sandwich-norm decoder: the stack
    by hand, layer after layer, through the same layers."""
    m = _model(total_ut_steps=1)
    ids = _prompts([24], seed=5)[0]
    h = m.word_embeddings(pt.to_tensor(jnp.asarray(ids[None])))
    for layer in m.layers:
        h = layer(h)
    plain = m.lm_head(m.final_norm(h)).value[0]
    looped = m(pt.to_tensor(jnp.asarray(ids[None]))).value[0]
    np.testing.assert_allclose(looped, plain, atol=1e-5)
    want = ref.forward_logits(weights, ids, dict(lw.sizes(CFG), passes=1))
    assert float(jnp.max(jnp.abs(looped - want))) < 1e-4
    # its cache entries are the ordinary ones, one plane
    cache = m.gen_decode_cache(1, 32, "float32", layout="paged",
                               block_size=8)
    assert cache[0].k.shape == (5, HEADS, 8, HEAD_DIM)
    got, _ = _cached_logits(m, ids, "paged")
    assert float(jnp.max(jnp.abs(got - want[23:]))) < 1e-4


def test_the_reference_controls_move_the_logits(weights):
    ids = _prompts([32], seed=4)[0]
    sizes = lw.sizes(CFG)
    sound = ref.forward_logits(weights, ids, sizes)
    for mode in ref.CONTROLS:
        moved = ref.forward_logits(weights, ids, sizes, mode)
        assert float(jnp.max(jnp.abs(moved - sound))) > 1e-2, mode
    # one pass fewer is the model of one pass fewer
    np.testing.assert_allclose(
        ref.forward_logits(weights, ids, sizes, "one_pass_fewer"),
        ref.forward_logits(weights, ids, dict(sizes, passes=PASSES - 1)),
        atol=1e-6)


# -- 2. the planes and the exit rule --------------------------------------------

@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_two_passes_read_different_planes(model, layout):
    """Overwriting plane 1 of every entry after the prompt moves the next
    step's pass 1 (and what follows it) and leaves pass 0 alone."""
    ids = _prompts([17], seed=8)[0]
    cache = model.gen_decode_cache(1, 32, "float32", layout=layout,
                                   block_size=8)
    _, cache = model(pt.to_tensor(jnp.asarray(ids[None, :16])), cache=cache)
    for r in range(PASSES):         # the prompt wrote every plane
        plane = cache[0].k[:, r * HEADS:(r + 1) * HEADS]
        assert float(jnp.max(jnp.abs(plane))) > 0
    heads = slice(HEADS, 2 * HEADS)
    spoiled = [c._replace(k=c.k.at[:, heads].set(0.5),
                          v=c.v.at[:, heads].set(-0.5)) for c in cache]
    step = pt.to_tensor(jnp.asarray(ids[None, 16:]))

    def states(cache):
        """s_r of the step, by running r + 1 passes of the same model."""
        out = []
        for r in range(PASSES):
            model.total_ut_steps = r + 1
            try:
                out.append(model.encode(step, cache)[0].value[0, 0])
            finally:
                model.total_ut_steps = PASSES
        return out

    sound, moved = states(cache), states(spoiled)
    np.testing.assert_array_equal(moved[0], sound[0])
    assert float(jnp.max(jnp.abs(moved[1] - sound[1]))) > 1e-3
    assert float(jnp.max(jnp.abs(moved[2] - sound[2]))) > 1e-3
    # and a step writes its position into every plane, the others as found
    _, after = model(step, cache=cache)
    k0, k1 = np.asarray(cache[1].k), np.asarray(after[1].k)
    assert int(after[1].index) == 17
    changed = np.argwhere(np.abs(k1 - k0).max(axis=-1) > 0)
    where = {(int(a[0]), int(a[2])) for a in changed}
    assert len(where) == 1          # one (slot or block, position)
    assert {int(a[1]) for a in changed} == set(range(PASSES * HEADS))


def test_the_exit_rule_against_a_hand_built_distribution():
    # lambda of three passes at four positions; the last pass's is not read
    lam = jnp.asarray([[0.5, 0.1, 0.0, 0.9],
                       [0.5, 0.2, 0.0, 0.5],
                       [0.3, 0.3, 0.9, 0.1]], jnp.float32)
    # p_0 = lam_0; p_1 = lam_1 (1 - lam_0); p_2 = the rest
    # cumulative: [.5 .75 1], [.1 .28 1], [0 0 1], [.9 .95 1]
    np.testing.assert_array_equal(exit_pass(lam, 0.0), [0, 0, 0, 0])
    np.testing.assert_array_equal(exit_pass(lam, 0.5), [0, 2, 2, 0])
    np.testing.assert_array_equal(exit_pass(lam, 0.7), [1, 2, 2, 0])
    np.testing.assert_array_equal(exit_pass(lam, 1.0), [2, 2, 2, 2])
    # a mass that never reaches the threshold in float32: the last pass
    np.testing.assert_array_equal(exit_pass(lam, 1.5), [2, 2, 2, 2])
    # one pass: all the mass, whatever the gate
    np.testing.assert_array_equal(exit_pass(lam[:1], 1.0), [0, 0, 0, 0])
    sel = ref.exit_select(jnp.arange(12.0).reshape(3, 4, 1), lam, 0.7)
    np.testing.assert_array_equal(sel[:, 0], [4.0, 9.0, 10.0, 3.0])


# -- 3. the loop in the program -------------------------------------------------

def _count(jaxpr, name):
    """Equations called ``name`` in ``jaxpr`` and under it, and the jaxprs
    of the loops met (``scan`` / ``while``)."""
    n, loops = 0, []
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    if eqn.primitive.name in ("scan", "while"):
                        loops.append((eqn, sub))
                    m, more = _count(sub, name)
                    n, loops = n + m, loops + more
    return n, loops


def test_the_passes_are_one_loop_in_the_compiled_decode_step(model):
    pool = _pool(model, route="pallas")
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    jaxpr = jax.make_jaxpr(pool._pool_decode)(
        params, bufs, pool._cache, np.zeros(n, np.int32), np.ones(n, bool),
        samp, np.zeros(n, np.uint32), np.zeros(n, np.int32)).jaxpr
    calls, loops = _count(jaxpr, "pallas_call")
    # one attention call site a layer, not a (pass, layer), and beside it
    # the layer's K/V write (``ops.pallas_decode._kv_write_call``)
    assert calls == 2 * LAYERS
    over_passes = [(e, sub) for e, sub in loops
                   if _count(sub, "pallas_call")[0]]
    assert len(over_passes) == 1
    eqn, body = over_passes[0]
    assert eqn.primitive.name == "scan" and eqn.params["length"] == PASSES
    assert _count(body, "pallas_call")[0] == 2 * LAYERS
    # the pools ride the loop as carries: K and V of every entry, whole
    pool_shape = pool._cache[0].k.shape
    carried = [v.aval.shape for v in body.invars[eqn.params["num_consts"]:]]
    assert carried.count(pool_shape) == 2 * LAYERS
    # the scopes of the loop, in the compiled step
    ids = _prompts([5])[0]
    pool.submit(ids, 3)
    pool.run()
    (exe,) = pool._decode_jit._exes.values()
    names = set(re.findall(r'op_name="([^"]*)"', exe.as_text()))
    # (between ``loop`` and the body's scopes jax writes its own
    # ``while/body/closed_call``)
    for scope in ("loop/.*/pass/layers/1/self_attn/q_proj/",
                  "loop/.*/final_norm/", "loop/.*/exit_gate/",
                  "loop/exit_select/",
                  "loop/.*/pass/layers/0/self_attn/cache_write/",
                  "loop/.*/pass/layers/0/self_attn/paged_attn/",
                  r"jit\(_pool_decode\)/lm_head/"):
        assert any(re.search(scope, n) for n in names), scope


# -- 4. served ------------------------------------------------------------------

def test_pool_prefill_then_decode_agrees_with_the_reference(model, weights):
    prompts = _prompts([13, 27, 9])
    got, pool = _serve(model, prompts, new=10)
    for i, prompt in enumerate(prompts):
        _assert_best(weights, prompt, [int(t) for t in got[i]])
    assert pool.compile_counts() == {"prefill": 2, "decode": 0,
                                     "pool_decode": 1, "slot_insert": 1}
    dense, _ = _serve(model, prompts, new=10, cache_layout="dense")
    kernel, _ = _serve(model, prompts, new=10, route="pallas")
    for i in got:
        np.testing.assert_array_equal(dense[i], got[i])
        np.testing.assert_array_equal(kernel[i], got[i])


def test_preempt_and_resume_carry_every_plane(model):
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
    assert info["blocks_spilled"] >= 1
    assert info["spill_bytes"] == info["blocks_spilled"] * 8 \
        * LAYERS * PASSES * PLANE_BYTES
    got = pool.run()
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    assert pool.compile_counts() == ref_pool.compile_counts()
    stats = pool.spill_stats()
    assert stats["preempts_total"] == 1 and stats["resumes_total"] == 1
    cs = pool.cache_stats()
    assert cs["free_blocks"] == cs["num_blocks"] - 1


def test_chunked_prefill_and_prefix_sharing_carry_every_plane(model):
    shared = _prompts([24], seed=12)[0]
    prompts = [np.concatenate([shared, tail])
               for tail in _prompts([5, 3, 7], seed=13)]
    want, _ = _serve(model, prompts, new=8)
    got, pool = _serve(model, prompts, new=8, prefill_chunk_tokens=8,
                       prefix_sharing=True)
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    assert pool.prefix_stats()["blocks_matched"] >= 3


def test_served_over_http_through_the_engine(model, weights):
    import urllib.request

    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend
    from paddle_tpu.serving import trace as engine_trace

    prompt = _prompts([14], seed=9)[0]
    engine = ServingEngine(model, max_len=64, slots=2, buckets=[16, 32],
                           cache_layout="paged", block_size=8,
                           cache_dtype="float32")
    front = ServingHTTPFrontend(engine)
    # an idle engine keeps ticking: a ring wide enough that the spans of
    # the one request are still in it when they are read
    tracer = engine_trace.Tracer(capacity=1 << 16)
    engine_trace.install(tracer)
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
        decodes = [e.meta for e in tracer.recorder.snapshot()
                   if e.name == "tick.decode"]
        toks = [l["token"] for l in lines if "token" in l]
        assert len(toks) == 7
        _assert_best(weights, prompt, toks)
        text = engine.metrics.render_prometheus().replace(".0\n", "\n")
        assert 'serving_cache_entries{layout="paged"} 2\n' in text
        assert "serving_cache_planes 6\n" in text
        steps = engine._pool.loop_passes // PASSES
        assert steps >= 6
        assert "serving_loop_passes_total %d\n" % (PASSES * steps) in text
        assert engine.metrics.snapshot()["serving_recoveries_total"] == 0
    finally:
        engine_trace.uninstall()
        front.shutdown()
        engine.shutdown(drain=False)
    assert decodes and all(
        m["passes"] == PASSES and m["kv_entries"] == LAYERS
        and m["kv_planes"] == LAYERS * PASSES and m["table_blocks"] == 2 * 8
        and 1 <= m["live_blocks"] <= m["live"] * 8 for m in decodes)


def test_stats_gauges_and_fingerprint_count_the_planes(model):
    cache = model.gen_decode_cache(2, 64, "float32", layout="paged",
                                   block_size=8, per_slot=True)
    layout = layout_of(cache)
    assert layout is get_layout("paged")        # the singleton, as it was
    per_slot = PASSES * LAYERS * 64 * PLANE_BYTES
    assert layout.bytes_per_slot_by_kind(cache, 2, 64) \
        == {"paged": (LAYERS, per_slot)}
    assert layout.entry_bytes_per_slot(cache[0], 2, 64) \
        == PASSES * 64 * PLANE_BYTES
    dense = model.gen_decode_cache(2, 64, "float32", layout="dense")
    assert layout_of(dense).bytes_per_slot_by_kind(dense, 2, 64) \
        == {"dense": (LAYERS, per_slot)}
    pool = _pool(model, slots=3)
    stats = pool.cache_stats()
    assert stats["passes"] == PASSES
    assert stats["cache_planes"] == LAYERS * PASSES
    assert stats["cache_entries"] == {"paged": LAYERS}
    assert stats["bytes_per_slot"] == {"paged": per_slot}
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["pool_bytes"] == stats["num_blocks"] * 8 \
        * PASSES * LAYERS * PLANE_BYTES
    fp = pool.config_fingerprint()
    assert fp["cache_planes"] == PASSES and fp["block_size"] == 8
    # a model of one plane says nothing new
    one = _pool(_model(total_ut_steps=1))
    assert "passes" not in one.cache_stats()
    assert "cache_planes" not in one.config_fingerprint()
    # at the published widths: 1,572,864 B a position
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "ouro-2p6b.json")))
    big = LoopedLM(**dict(lw.model_kwargs(cfg), num_layers=1,
                          vocab_size=8))
    shapes = jax.eval_shape(lambda: big.gen_decode_cache(
        16, 320, "bfloat16", per_slot=True, layout="paged", block_size=64,
        num_blocks=81))
    assert shapes[0].k.shape == (81, 64, 64, 128)
    assert 48 * get_layout("paged").entry_bytes_per_slot(
        shapes[0], 16, 320) == 320 * 1572864


# -- 5. refusals ----------------------------------------------------------------

REFUSED = {
    "int8_planes": (dict(cache_dtype="int8"),
                    "int8.*3 K/V planes.*one a pass"),
    "disk_spill": (dict(spill_tier="disk", spill_dir="unused"),
                   "spill_tier='disk'.*3 K/V planes"),
    "ptkv_hand_off": (dict(prefill_only=True, spill_tier="disk",
                           spill_dir="unused"),
                      "spill_tier='disk'.*3 K/V planes"),
    "recurrent_layout": (dict(cache_layout="recurrent"),
                         "LoopedLM supports cache_layouts"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_the_planes_cannot_carry_is_refused_by_name(model, feature,
                                                         tmp_path):
    kw, match = REFUSED[feature]
    if "spill_dir" in kw:
        kw = dict(kw, spill_dir=str(tmp_path))
    with pytest.raises(InvalidArgumentError, match=match):
        _pool(model, **kw)


def test_the_session_the_mesh_and_the_model_name_what_they_refuse(model):
    from paddle_tpu.jit.mesh import DecodeMesh

    with pytest.raises(InvalidArgumentError, match="int8.*3 K/V planes"):
        DecodeSession(model, max_len=64, buckets=[16], cache_dtype="int8")
    with pytest.raises(InvalidArgumentError,
                       match="mp=2 cannot shard a cache entry of 3 K/V "
                             "planes"):
        DecodeMesh(1, 2).validate_model(model)
    DecodeMesh(2, 1).validate_model(model)          # slots over dp: fine
    with pytest.raises(InvalidArgumentError, match="keeps K/V in one of"):
        model.gen_decode_cache(1, 64, layout="recurrent")
    with pytest.raises(InvalidArgumentError, match="total_ut_steps"):
        LoopedLM(**dict(lw.model_kwargs(CFG), total_ut_steps=0))
    with pytest.raises(InvalidArgumentError, match="planes >= 1"):
        model.layers[0].self_attn.gen_decode_cache(1, 8, planes=0)
