"""Gated power retention (``ops/power_retention.py``, ``nn.PowerRetention``,
``models.PowerRetentionLM``) served through the recurrent cache layout.

At small widths on the CPU (2 layers, width 64, 4 query heads on 2 K/V
heads of 16, the benchmark's seeded weights with the gate's initialiser):

1. the two served forms against the quadratic definition and against each
   other, identity steps on padded positions and free slots;
2. prefill then decode through ``GenerationPool`` against the plain
   reference's full forward (``benchmark/harness/retention_reference.py``);
3. what a pool does to a slot's state: splice, freeze, preempt and resume
   through both spill tiers, two requests side by side;
4. every refused feature raises its typed error; the step is launched
   ahead; the spans and gauges the tracing reads are there.

Tolerances: everything here is float32 on the CPU backend, where a float32
matmul is float32.  An output is a quotient of two sums of a few hundred
terms, and after few positions the denominator can be small (one position's
``(q . k)^2``), so two orders of summation differ by up to 5e-5 on values of
scale 1 (read here); the gates below are 2e-4 on values and states and 1e-4
on logits, where the norms after the mixer damp it.
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
from paddle_tpu.jit.cache import get_layout
from paddle_tpu.models import PowerRetentionLM, TransformerLM
from paddle_tpu.ops import power_retention as pr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import retention_reference as ref  # noqa: E402
from harness import retention_weights as rw  # noqa: E402

TOL = 2e-4
CFG = dict(vocab_size=97, hidden_size=64, num_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           intermediate_size=128, rope_theta=1e6, rms_norm_eps=1e-6,
           weights_dtype="float32",
           assumed={"normaliser_eps": 1e-6, "gate_memory": [8, 64]})
SEED = 11


def _qkv(t, b=2, hq=4, hkv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, t, d)) * d ** -0.5
    k = jax.random.normal(ks[1], (b, hkv, t, d))
    v = jax.random.normal(ks[2], (b, hkv, t, d))
    lg = jax.nn.log_sigmoid(3.0 + jax.random.normal(ks[3], (b, hkv, t)))
    return q, k, v, lg


def _zeros(b=2, hkv=2, d=16):
    n = pr.phi_size(d)
    return jnp.zeros((b, hkv, d, n)), jnp.zeros((b, hkv, 1, n))


def _steps(q, k, v, lg, route, keep=None):
    s, z = _zeros(q.shape[0], k.shape[1], q.shape[-1])
    ys = []
    for t in range(q.shape[2]):
        y, s, z = pr.power_retention_step(
            q[:, :, t], k[:, :, t], v[:, :, t], lg[:, :, t], s, z,
            None if keep is None else keep[:, t], route=route)
        ys.append(y)
    return jnp.stack(ys, 2), s, z


def test_phi_is_the_symmetric_square():
    a, b = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 32))
    got = jnp.sum(pr.symmetric_square(a) * pr.symmetric_square(b), -1)
    np.testing.assert_allclose(got, jnp.sum(a * b, -1) ** 2, rtol=1e-5)
    assert pr.phi_size(128) == 9216 and pr.phi_size(16) == 256
    with pytest.raises(InvalidArgumentError, match="multiple of 16"):
        pr.phi_size(24)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_both_ways_to_phi_give_the_same_numbers(dtype):
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 32)).astype(dtype)
    a, b = pr._phi_by_matmul(u), pr._phi_by_broadcast(u)
    assert a.dtype == b.dtype == jnp.float32 and a.shape == (3, 5, 768)
    assert bool(jnp.all(a == b))


@pytest.mark.parametrize("route", ["composition", "pallas"])
def test_step_form_matches_the_quadratic_definition(route):
    q, k, v, lg = _qkv(19)
    want = pr.power_retention_quadratic(q, k, v, lg)
    got, _, _ = _steps(q, k, v, lg, route)
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


@pytest.mark.parametrize("chunk", [4, 8, 5, 7, 64, None])
def test_chunked_form_matches_the_step_form(chunk):
    # 24 positions: chunks of 4 and 8 divide them, 5 and 7 do not (the
    # last chunk is padded with identity steps), 64 and None (the whole
    # sequence) make one chunk
    q, k, v, lg = _qkv(24, seed=3)
    y_step, s_step, z_step = _steps(q, k, v, lg, "composition")
    y, s, z = pr.power_retention_chunked(q, k, v, lg, *_zeros(),
                                         chunk=chunk)
    assert float(jnp.max(jnp.abs(y - y_step))) <= TOL
    assert float(jnp.max(jnp.abs(s - s_step))) <= TOL
    assert float(jnp.max(jnp.abs(z - z_step))) <= TOL


@pytest.mark.parametrize("form", ["chunked", "step"])
def test_padded_positions_are_identity_steps(form):
    # row 0 holds 13 real positions of a bucket of 24, row 1 all of them
    q, k, v, lg = _qkv(24, seed=5)
    keep = jnp.arange(24)[None, :] < jnp.asarray([13, 24])[:, None]
    if form == "chunked":
        _, s, z = pr.power_retention_chunked(q, k, v, lg, *_zeros(),
                                             keep=keep, chunk=8)
    else:
        _, s, z = _steps(q, k, v, lg, "composition", keep)
    _, s13, z13 = pr.power_retention_chunked(
        q[:, :, :13], k[:, :, :13], v[:, :, :13], lg[:, :, :13], *_zeros())
    assert float(jnp.max(jnp.abs(s[0] - s13[0]))) <= TOL
    assert float(jnp.max(jnp.abs(z[0] - z13[0]))) <= TOL
    if form == "step":      # an identity step is exact, not merely close
        _, s12, z12 = _steps(q[:, :, :13], k[:, :, :13], v[:, :, :13],
                             lg[:, :, :13], "composition")
        assert bool(jnp.all(s[0] == s12[0])) and bool(jnp.all(z[0]
                                                              == z12[0]))


@pytest.mark.parametrize("route", ["pallas", "composition"])
def test_prefill_from_an_empty_state_matches_the_other_forms(route,
                                                             monkeypatch):
    # the form a server's prefill takes: outputs by the quadratic kernel
    # (interpreted here; blocks of 128 so that 384 positions are three,
    # with key blocks skipped above the diagonal), the state from phi(k)
    # alone in chunks of 256 (the last one half padding).  Row 0 holds 300
    # real positions of the 384
    monkeypatch.setattr(pr, "PREFILL_BLOCK", 128)
    monkeypatch.setattr(pr, "STATE_CHUNK", 256)
    q, k, v, lg = _qkv(384, b=2, hq=2, hkv=1, d=128, seed=7)
    keep = jnp.arange(384)[None, :] < jnp.asarray([300, 384])[:, None]
    scale = 128 ** -0.5
    y, s, z = pr.power_retention_prefill(q, k, v, lg, keep, route=route,
                                         scale=scale)
    want = pr.power_retention_quadratic(q, k, v, lg, scale=scale)
    assert float(jnp.max(jnp.abs(y[1] - want[1]))) <= TOL
    assert float(jnp.max(jnp.abs(y[0, :, :300] - want[0, :, :300]))) <= TOL
    _, s_c, z_c = pr.power_retention_chunked(
        q, k, v, lg, *_zeros(2, 1, 128), keep=keep, scale=scale)
    # the state sums a few hundred products of size up to 50
    assert float(jnp.max(jnp.abs(s - s_c))) <= 1e-3
    assert float(jnp.max(jnp.abs(z - z_c))) <= 1e-3
    assert pr.prefill_kernel_refusal((1, 40, 4096, 128)) is None
    assert "128 lanes" in pr.prefill_kernel_refusal((1, 4, 256, 16))
    assert "whole blocks" in pr.prefill_kernel_refusal((1, 4, 1000, 128))


def test_state_must_be_float32():
    q, k, v, lg = _qkv(1)
    s, z = _zeros()
    with pytest.raises(InvalidArgumentError, match="float32"):
        pr.power_retention_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                lg[:, :, 0], s.astype(jnp.bfloat16), z)


# -- the model through the pool --------------------------------------------

@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = PowerRetentionLM(**rw.model_kwargs(CFG))
    m.eval()
    rw.load_into(m, CFG, SEED)
    return m


@pytest.fixture(scope="module")
def weights():
    return rw.make_weights(CFG, SEED)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _pool(model, slots=2, **kw):
    kw.setdefault("buckets", [16, 32])
    return GenerationPool(model, max_len=64, slots=slots,
                          cache_layout="recurrent", **kw)


def _serve(model, prompts, new=8, **kw):
    pool = _pool(model, **kw)
    for i, ids in enumerate(prompts):
        pool.submit(ids, new, request_id=i)
    return pool.run(), pool


def test_cached_forward_agrees_with_the_reference_on_logits(model, weights):
    ids = _prompts([29])[0]
    want = ref.forward_logits(weights, ids, rw.sizes(CFG))
    cache = model.gen_decode_cache(1, 64)
    lg, cache = model(pt.to_tensor(ids[None, :17]), cache=cache)
    got = [lg.value[0]]
    for t in range(17, 29):
        lg, cache = model(pt.to_tensor(ids[None, t:t + 1]), cache=cache)
        got.append(lg.value[0])
    assert float(jnp.max(jnp.abs(jnp.concatenate(got) - want))) <= 1e-4


def test_pool_prefill_then_decode_agrees_with_the_reference(model, weights):
    # through GenerationPool: bucketed prefill (13 and 27 real positions
    # of buckets of 16 and 32), the splice, the batched step.  Every
    # served token is the reference's best at its position, or within
    # 1e-4 of it in the reference's own logits (a near tie)
    prompts = _prompts([13, 27, 9])
    got, pool = _serve(model, prompts, new=10)
    for i, prompt in enumerate(prompts):
        toks = [int(t) for t in got[i]]
        seq = np.concatenate([prompt, toks[:-1]])
        logits = ref.forward_logits(weights, seq, rw.sizes(CFG))
        rows = logits[len(prompt) - 1:]
        gap = jnp.max(rows, -1) - rows[jnp.arange(len(toks)),
                                       jnp.asarray(toks)]
        assert float(jnp.max(gap)) <= 1e-4
    assert pool.compile_counts()["pool_decode"] == 1


def test_two_requests_do_not_touch_each_others_state(model):
    a, b = _prompts([21, 6], seed=4)
    alone, _ = _serve(model, [a], new=12)
    both, _ = _serve(model, [a, b], new=12)
    np.testing.assert_array_equal(both[0], alone[0])
    other, _ = _serve(model, [b], new=12)
    np.testing.assert_array_equal(both[1], other[0])


def test_a_free_slots_state_comes_through_the_step_untouched(model):
    # ``begin_step`` closes the free slot's update window: its S, z and
    # index leave ``_pool_decode`` bit for bit as they entered, with no
    # select over the state
    pool = _pool(model, slots=3)
    params, bufs = pool._session._state_vals()
    rng = np.random.default_rng(0)
    cache = [c._replace(
        state=jnp.asarray(rng.normal(size=c.state.shape), jnp.float32),
        norm=jnp.asarray(rng.random(size=c.norm.shape), jnp.float32),
        index=jnp.asarray([5, 7, 9], jnp.int32)) for c in pool._cache]
    n = pool.slots
    active = np.asarray([True, False, True])
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    new, tok, _ = pool._pool_decode(
        params, bufs, cache, jnp.asarray([3, 4, 5], jnp.int32), active,
        samp, np.zeros(n, np.uint32), np.zeros(n, np.int32))
    for old, c in zip(cache, new):
        assert bool(jnp.all(c.state[1] == old.state[1]))
        assert bool(jnp.all(c.norm[1] == old.norm[1]))
        assert not bool(jnp.all(c.state[0] == old.state[0]))
        assert c.index.tolist() == [6, 7, 10]
        assert c.limit.shape == () and int(c.limit) == 64
    assert int(tok[1]) == 0


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_preempt_and_resume_carry_the_state(model, tier, tmp_path):
    prompts = _prompts([5, 19, 11], seed=2)
    kw = {} if tier == "host" else dict(spill_tier="disk",
                                        spill_dir=str(tmp_path))
    want, ref_pool = _serve(model, prompts, **kw)
    counts = ref_pool.compile_counts()
    pool = _pool(model, **kw)
    for i, ids in enumerate(prompts):
        pool.submit(ids, 8, request_id=i)
    pool.step()
    pool.step()
    assert pool.can_preempt(0)
    info = pool.preempt(0)
    per_layer = pr.state_bytes(2, 16, 16)
    assert info["state_bytes"] == 2 * per_layer == info["spill_bytes"]
    if tier == "disk":
        assert os.listdir(str(tmp_path)), "no transfer file written"
    got = pool.run()
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    assert pool.compile_counts() == counts
    ss = pool.spill_stats()
    assert ss["preempts_total"] == 1 and ss["resumes_total"] == 1


def test_a_toy_recurrences_spill_cannot_be_adopted(model):
    from paddle_tpu import nn
    toy = nn.SSMLM(vocab_size=97, hidden_size=32, num_layers=2, d_state=48)
    toy.eval()
    a = GenerationPool(toy, max_len=64, slots=2, buckets=[32],
                       cache_layout="recurrent").config_fingerprint()
    b = _pool(model).config_fingerprint()
    assert a["state_shapes"] == [[48]]
    assert b["state_shapes"] == [[2, 16, 256], [2, 1, 256]]
    assert a["cache_layout"] == b["cache_layout"] == "recurrent"


REFUSED = {
    "prefix_sharing": (dict(prefix_sharing=True), "prefix_sharing.*recurr"),
    "chunked_prefill": (dict(prefill_chunk_tokens=8),
                        "prefill_chunk_tokens.*recurrent"),
    "num_blocks": (dict(num_blocks=16), "num_blocks"),
    "bfloat16_state": (dict(cache_dtype="bfloat16"), "float32"),
    "int8_state": (dict(cache_dtype="int8"), "float32"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_cannot_carry_over_is_refused_by_name(model, feature):
    kw, match = REFUSED[feature]
    with pytest.raises(InvalidArgumentError, match=match):
        _pool(model, **kw)


def test_other_layouts_and_pools_are_refused_by_name(model, tmp_path):
    for layout in ("dense", "paged"):
        with pytest.raises(InvalidArgumentError, match="PowerRetentionLM"):
            DecodeSession(model, max_len=64, cache_layout=layout)
    with pytest.raises(InvalidArgumentError, match="prefill_only.*recurr"):
        _pool(model, prefill_only=True, spill_tier="disk",
              spill_dir=str(tmp_path))
    draft = TransformerLM(vocab_size=97, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=64,
                          max_position=64, causal=True, dropout=0.0)
    with pytest.raises(InvalidArgumentError, match="speculative.*recurr"):
        SpeculativePool(model, draft, max_len=64, cache_layout="recurrent")
    with pytest.raises(InvalidArgumentError, match="recurrent"):
        model.gen_decode_cache(1, 64, layout="paged")
    layout = get_layout("recurrent")
    assert layout.state_fields(model.gen_decode_cache(1, 8)[0]) \
        == ("state", "norm")


def test_the_step_is_launched_ahead_and_the_spans_say_what_it_moves(model):
    from paddle_tpu.serving import trace as engine_trace

    tracer = engine_trace.Tracer(capacity=4096)
    engine_trace.install(tracer)
    try:
        pool = _pool(model)
        assert pool._depth == 1
        for i, ids in enumerate(_prompts([13, 27])):
            pool.submit(ids, 6, request_id=i)
        pool.step()
        pool.step()
        assert pool._flights, "no step in flight after a tick"
        pool.run()
    finally:
        engine_trace.uninstall()
    spans = [(e.name, e.meta or {}) for e in tracer.recorder.snapshot()]
    per_slot = 2 * pr.state_bytes(2, 16, 16)
    decodes = [m for n, m in spans if n == "tick.decode"]
    assert decodes and all(m["state_bytes"] == m["live"] * per_slot
                           for m in decodes)
    assert any(m["ahead"] == 1 for m in decodes)
    prefills = {m["bucket"]: m["chunks"] for n, m in spans
                if n == "tick.prefill"}
    assert prefills == {16: 1, 32: 1}
    assert PowerRetentionLM.prefill_chunks(4096) == 8
    stats = pool.cache_stats()
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["pool_bytes"] == 2 * per_slot


def test_served_over_http_through_the_engine(model):
    import urllib.request

    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    prompt = _prompts([14], seed=9)[0]
    want, _ = _serve(model, [prompt], new=7)
    engine = ServingEngine(model, max_len=64, slots=2, buckets=[16, 32],
                           cache_layout="recurrent")
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
        assert toks == [int(t) for t in want[0]]
        text = engine.metrics.render_prometheus()
        assert "serving_state_bytes_per_slot %d" % (
            2 * pr.state_bytes(2, 16, 16)) in text.replace(".0", "")
    finally:
        front.shutdown()
        engine.shutdown(drain=False)
