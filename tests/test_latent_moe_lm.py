"""A latent-attention decoder with routed experts and a shared expert
(``models.LatentMoELM``) served through a cache whose entries are latents
(``jit.cache.LatentLayout``).

At small widths on the CPU, float32 (3 layers of width 64, the first dense;
4 heads on one latent of 128 + 8; 16 experts in 4 groups, the 2 best groups,
4 a token, 8 of them held from expert 4; a shared expert; 256 rows of
vocabulary; the benchmark's seeded weights):

1. the model's forward and its cached forward against the plain reference's
   logits (``benchmark/harness/latent_reference.py``), and the reference's
   two controls move them;
2. ``GenerationPool`` and ``ServingEngine`` over HTTP: prefill in the
   expanded form, the splice, the batched absorbed step, every served token
   the reference's best; preempt and resume in memory carry the latents;
3. what the hooks say: the entries' kind, ``cache_stats()``, the gauges, the
   ``tick.decode`` span; the step retraces nothing;
4. what a latent entry does not carry is refused by a typed error that
   names it: an int8 pool, the disk tier, PTKV hand-off, prefix sharing,
   chunked prefill.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.inference import GenerationPool
from paddle_tpu.jit.cache import (LatentLayout, entry_layout, get_layout,
                                  layout_of)
from paddle_tpu.models import LatentMoELM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import latent_reference as ref  # noqa: E402
from harness import latent_weights as lw  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "toy-latent.json")) as _f:
    CFG = json.load(_f)
SEED = 11
ENTRY_BYTES = 256 * 4           # a position a layer: 128 + 8, padded to 256


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LatentMoELM(**lw.model_kwargs(CFG))
    m.eval()
    lw.load_into(m, CFG, SEED)
    return m


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


def _assert_best(weights, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]])
    logits = ref.forward_logits(weights, seq, lw.sizes(CFG))
    rows = logits[len(prompt) - 1:]
    gap = jnp.max(rows, -1) - rows[jnp.arange(len(toks)), jnp.asarray(toks)]
    assert float(jnp.max(gap)) <= 1e-4
    assert len(set(toks)) > 2, "a model that repeats one token"


# -- 1. against the reference ---------------------------------------------------

def test_the_model_is_one_dense_layer_then_expert_layers_with_a_share(model):
    assert [layer.dense for layer in model.layers] == [True, False, False]
    moe = model.layers[1].moe
    assert moe.held == (4, 8) and moe.router.shape == [64, 16]
    assert moe.w_gate.shape == [8, 64, 32] and moe.shared is not None
    assert (moe.scoring, moe.n_group, moe.topk_group, moe.routed_scale) \
        == ("sigmoid", 4, 2, 2.5)
    assert model.lm_head.weight.shape == [64, 256]
    names = {n for n, _ in model.named_parameters()}
    assert "layers.0.mlp.gate_proj.weight" in names
    assert "layers.1.moe.shared.down_proj.weight" in names
    assert not any("absorb" in n or "w_uk" in n for n in names)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cached_forward_agrees_with_the_reference_on_logits(model, weights,
                                                            layout):
    ids = _prompts([40], seed=3)[0]
    want = ref.forward_logits(weights, ids, lw.sizes(CFG))
    full = model(pt.to_tensor(jnp.asarray(ids[None]))).value[0]
    assert float(jnp.max(jnp.abs(full - want))) < 1e-4
    assert float(jnp.max(jnp.abs(want))) > 1.0
    cache = model.gen_decode_cache(1, 48, "float32", layout=layout,
                                   block_size=8)
    # a prompt (expanded), its last logits alone; then steps (absorbed)
    lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, :24])), cache=cache,
                      last=23)
    got = [lg.value[0, 0]]
    for t in range(24, 40):
        lg, cache = model(pt.to_tensor(jnp.asarray(ids[None, t:t + 1])),
                          cache=cache)
        got.append(lg.value[0, 0])
    assert float(jnp.max(jnp.abs(jnp.stack(got) - want[23:]))) < 1e-4


def test_the_reference_controls_move_the_logits(weights):
    ids = _prompts([32], seed=4)[0]
    sound = ref.forward_logits(weights, ids, lw.sizes(CFG))
    for mode in ("fp8", "no_group_limit"):
        moved = ref.forward_logits(weights, ids, lw.sizes(CFG), mode)
        assert float(jnp.max(jnp.abs(moved - sound))) > 0.05, mode
    # the held share matters: another share of the experts, other logits
    other = ref.forward_logits(weights, ids,
                               dict(lw.sizes(CFG), first_held=0))
    assert float(jnp.max(jnp.abs(other - sound))) > 0.05


# -- 2. through the pool and the engine -----------------------------------------

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


def test_the_prompt_runs_expanded_and_every_chunk_after_it_absorbed(
        model, monkeypatch):
    """What each compiled program traced: a bucket's prefill the expanded
    form in every layer (its cache is made in the same trace and stands at
    0), the pool's step the absorbed form; and a speculative pool whose
    verify chunk (``spec_k`` + 1 = 9 positions) is longer than the kernel's
    chunk verifies absorbed, against the cached context, and serves what
    the plain pool serves."""
    import importlib

    from paddle_tpu.inference import SpeculativePool

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    seen = {"causal_attention": 0, "latent_decode_attention": 0}
    for name in seen:
        plain = getattr(fa, name)

        def counted(*args, _plain=plain, _name=name, **kwargs):
            seen[_name] += 1
            return _plain(*args, **kwargs)
        monkeypatch.setattr(fa, name, counted)
    prompts = _prompts([13, 27, 9], seed=3)
    want, pool = _serve(model, prompts, new=20)
    layers = CFG["num_layers"]
    assert pool.compile_counts()["prefill"] == 2
    assert seen == {"causal_attention": 2 * layers,
                    "latent_decode_attention": layers}
    spec = SpeculativePool(model, model, max_len=64, spec_k=8, slots=2,
                           cache_layout="paged", block_size=8,
                           buckets=[16, 32], cache_dtype="float32")
    for i, ids in enumerate(prompts):
        spec.submit(ids, 20, request_id=i)
    got = spec.run()
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    stats = spec.acceptance_stats()
    # the draft is the target (its entries latents too): the verified
    # chunks agree with it but at a near-tie of two logits
    assert stats["drafted"] > 0
    assert stats["accepted"] >= 0.9 * stats["drafted"]


def test_preempt_and_resume_carry_the_latents(model):
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
    assert info["spill_bytes"] == info["blocks_spilled"] * 8 * 3 \
        * ENTRY_BYTES
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
        assert 'serving_cache_entries{layout="latent"} 3\n' in text
        assert ('serving_moe_experts_read_expected{held="16",'
                'route="every"} 16\n') in text
        assert text.count("# TYPE serving_cache_entries gauge") == 1
        assert "serving_kv_free_blocks" in text
        assert engine.cache_stats()["cache_entries"] == {"latent": 3}
    finally:
        front.shutdown()
        engine.shutdown(drain=False)


# -- 3. what the hooks say -------------------------------------------------------

def test_the_entries_are_latents_behind_the_paged_allocator(model):
    cache = model.gen_decode_cache(2, 64, "float32", per_slot=True,
                                   layout="paged", block_size=8,
                                   num_blocks=9)
    lay = layout_of(cache)
    assert isinstance(lay, LatentLayout) and lay is entry_layout(cache[0])
    assert lay.name == "latent" and lay.paged and lay.positional \
        and lay.spillable and not lay.transferable and not lay.recurrent \
        and lay.prompt_from_zero
    assert lay.payload_fields(cache[0]) == ("latent",)
    assert cache[0].latent.shape == (9, 8, 256)
    assert lay.field_axes("latent") == ("dp", None)
    assert lay.field_axes("table") == lay.field_axes("index") == ("dp",)
    with pytest.raises(InvalidArgumentError, match="layout 'latent'"):
        lay.field_axes("k")
    assert lay.cache_dtype_str(cache) == "float32"
    # a latent by slot is a dense entry like K/V by slot
    dense = model.gen_decode_cache(2, 64, "float32")
    assert layout_of(dense) is get_layout("dense")
    assert get_layout("dense").payload_fields(dense[0]) == ("latent",)
    # the K/V layouts read their payload the same way
    from paddle_tpu.nn import MultiHeadAttention
    kv = MultiHeadAttention(32, 2).gen_decode_cache(
        1, 16, "int8", layout="paged", block_size=8)
    assert get_layout("paged").payload_fields(kv) == (
        "k", "v", "k_scale", "v_scale")
    kv = MultiHeadAttention(32, 2).gen_decode_cache(1, 16, "float32")
    assert get_layout("dense").payload_fields(kv) == ("k", "v")


def test_cache_stats_spans_and_no_retrace(model):
    from paddle_tpu.serving import trace as engine_trace

    tracer = engine_trace.Tracer(capacity=4096)
    engine_trace.install(tracer)
    try:
        pool = _pool(model, slots=3)
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
        m["latent_entries"] == 3 and m["table_blocks"] == 3 * 8
        and 1 <= m["live_blocks"] <= m["live"] * 8
        and "state_bytes" not in m
        # what the block counts run over is said in every paged trace
        and m["kv_entries"] == m["kv_planes"] == 3
        for m in decodes)
    assert any(m["ahead"] == 1 for m in decodes)
    # two expert layers of 8 held: at these widths a skipped read saves
    # nothing, every expert runs on every row and all 16 are read
    assert all(m["moe_route"] == "every" and m["experts_held"] == 16
               and m["experts_read_expected"] == 16.0 for m in decodes)
    stats = pool.cache_stats()
    assert (stats["moe_route"], stats["experts_held"],
            stats["experts_read_expected"]) == ("every", 16, 16.0)
    assert stats["cache_layout"] == pool.cache_layout == "latent"
    assert stats["cache_entries"] == {"latent": 3}
    assert stats["bytes_per_slot"] == {"latent": 3 * 64 * ENTRY_BYTES}
    assert stats["state_bytes_per_slot"] == 3 * 64 * ENTRY_BYTES
    assert stats["dense_equiv_bytes"] == 3 * 3 * 64 * ENTRY_BYTES
    assert stats["pool_bytes"] == stats["num_blocks"] * 8 * 3 * ENTRY_BYTES
    assert stats["reachable_bytes"] == 0            # nothing mapped
    assert stats["cache_dtype"] == "float32"
    assert pool.config_fingerprint()["cache_layout"] == "latent"
    # a K/V model's figures are what they were
    from paddle_tpu.models import TransformerLM
    plain = TransformerLM(vocab_size=97, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=64,
                          max_position=64, causal=True, dropout=0.0)
    plain.eval()
    p = GenerationPool(plain, max_len=64, slots=2, buckets=[16],
                       cache_layout="paged", block_size=8)
    assert p.cache_stats()["dense_equiv_bytes"] \
        == 2 * 64 * 2 * 2 * 16 * 4
    assert "moe_route" not in p.cache_stats()


def test_served_tokens_are_the_same_on_the_touched_route(model, weights,
                                                         monkeypatch):
    """Where a skipped read is given a price of nothing the expert layers
    run only the experts some row chose, prompts and steps: the served
    tokens are those of every expert on every row, and the reference's
    best; the span says which route the step compiled to and what its
    live rows are expected to read."""
    from paddle_tpu.nn.functional import moe
    from paddle_tpu.serving import trace as engine_trace

    prompts = _prompts([13, 27, 9], seed=4)
    every, _ = _serve(model, prompts, new=10)
    monkeypatch.setattr(moe, "_SKIP_COST_S", 0.0)
    tracer = engine_trace.Tracer(capacity=4096)
    engine_trace.install(tracer)
    try:
        touched, pool = _serve(model, prompts, new=10)
    finally:
        engine_trace.uninstall()
    for i, prompt in enumerate(prompts):
        assert [int(t) for t in touched[i]] == [int(t) for t in every[i]]
        _assert_best(weights, prompt, [int(t) for t in touched[i]])
    decodes = [e.meta for e in tracer.recorder.snapshot()
               if e.name == "tick.decode"]
    # 4 of 16 a token: a row leaves 3/4 of the experts alone
    assert decodes and all(
        m["moe_route"] == "touched" and m["experts_held"] == 16
        and m["experts_read_expected"]
        == pytest.approx(16 * (1 - 0.75 ** m["live"])) for m in decodes)
    assert {m["live"] for m in decodes} == {1, 2}
    assert pool.cache_stats()["experts_read_expected"] == 0.0   # idle


# -- 4. refusals -----------------------------------------------------------------

REFUSED = {
    "int8_pool": (dict(cache_dtype="int8"),
                  "latent cache entry is kept in one of.*not 'int8'"),
    "disk_spill": (dict(spill_tier="disk", spill_dir="unused"),
                   "spill_tier='disk'.*'latent' keeps a latent entry"),
    "ptkv_hand_off": (dict(prefill_only=True, spill_tier="disk",
                           spill_dir="unused"),
                      "spill_tier='disk'.*'latent' keeps a latent entry"),
    "prefix_sharing": (dict(prefix_sharing=True, prefill_chunk_tokens=8),
                       "prefill_chunk_tokens cannot apply.*'latent'.*"
                       "starts at position 0"),
    "prefix_sharing_alone": (dict(prefix_sharing=True),
                             "prefix_sharing cannot apply.*'latent'"),
    "chunked_prefill": (dict(prefill_chunk_tokens=8),
                        "prefill_chunk_tokens cannot apply.*'latent'"),
    "recurrent_layout": (dict(cache_layout="recurrent"),
                         "LatentMoELM supports cache_layouts"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_a_latent_entry_does_not_carry_is_refused_by_name(model,
                                                               feature):
    kwargs, message = REFUSED[feature]
    with pytest.raises(InvalidArgumentError, match=message):
        _pool(model, **kwargs)


def test_the_model_refuses_what_it_cannot_build():
    with pytest.raises(InvalidArgumentError, match="first_k_dense"):
        LatentMoELM(**dict(lw.model_kwargs(CFG), first_k_dense=4))
    m = LatentMoELM(**lw.model_kwargs(CFG))
    with pytest.raises(InvalidArgumentError, match="keeps its latents"):
        m.gen_decode_cache(1, 16, "float32", layout="recurrent")
