"""A decoder that mixes Mamba layers and attention layers
(``ops/selective_scan.py``, ``nn.MambaMixer``, ``models.HybridMambaLM``)
served through a cache list with two kinds of entry.

At small widths on the CPU (4 layers of width 64, one of them attention with
4 query heads on 1 K/V head of 16; ``d_inner`` 128, 16 states a channel,
convolution of 4; the benchmark's seeded weights with the recurrence's own
initialiser):

1. the three forms of the scan against each other (the kernels under the
   interpreter), identity steps at ``dt = 0``;
2. the cached forward and ``GenerationPool`` (prefill, splice, batched step,
   through both kinds of entry) against the plain reference's full forward
   (``benchmark/harness/mamba_reference.py``);
3. what the hooks do to a mixed cache list: a padded bucket leaves the state
   of the true length, the splice, a free slot through a step, preempt and
   resume carrying ``conv``, ``ssm`` and the K/V blocks;
4. every refused feature raises a typed error that names the recurrent
   layers; the step is launched ahead and retraces nothing over joins and
   leaves; the spans, stats and gauges say what each kind holds.

Everything here is float32 on the CPU backend: two orders of summation move
a logit by 1e-6, the gates below are 1e-4.
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
from paddle_tpu.models import HybridMambaLM, TransformerLM
from paddle_tpu.ops import selective_scan as ss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import mamba_reference as ref  # noqa: E402
from harness import mamba_weights as mw  # noqa: E402

CFG = dict(vocab_size=97, hidden_size=64, num_layers=4,
           attn_layer_period=4, attn_layer_offset=1,
           num_attention_heads=4, num_key_value_heads=1,
           intermediate_size=128, mamba_expand=2, mamba_d_state=16,
           mamba_d_conv=4, mamba_dt_rank=8, rms_norm_eps=1e-6,
           weights_dtype="float32",
           assumed={"dt_init": [0.001, 0.1], "initializer_std": 0.1})
SEED = 11
SSM_BYTES, CONV_BYTES = 16 * 128 * 4, 3 * 128 * 4       # a slot a layer
KV_BYTES = 2 * 16 * 4                                   # a position


# -- 1. the scan's three forms ------------------------------------------------

def _scan_inputs(rows=2, t=32, c=256, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (0.1 * jax.random.uniform(ks[0], (rows, t, c)),
            jax.random.normal(ks[1], (rows, t, c)),
            jax.random.normal(ks[2], (rows, t, n)),
            jax.random.normal(ks[3], (rows, t, n)),
            -jnp.exp(0.5 * jax.random.normal(ks[4], (n, c))),
            jax.random.normal(ks[5], (c,)),
            jax.random.normal(ks[6], (rows, n, c)))


def _steps(dt, c, b, cm, a, d, s, route):
    ys = []
    for t in range(dt.shape[1]):
        y, s = ss.selective_scan_step(dt[:, t], c[:, t], b[:, t], cm[:, t],
                                      a, d, s, route=route)
        ys.append(y)
    return jnp.stack(ys, 1), s


@pytest.mark.parametrize("form", ["prefill_kernel", "step_kernel",
                                  "step_composition"])
def test_every_form_of_the_scan_agrees_with_the_sequential_one(form):
    args = _scan_inputs()
    want_y, want_s = ss.selective_scan_reference(*args)
    if form == "prefill_kernel":
        y, s = ss.selective_scan_prefill(*args, route="pallas")
    else:
        y, s = _steps(*args, route="pallas" if form == "step_kernel"
                      else "composition")
    assert float(jnp.max(jnp.abs(y - want_y))) <= 1e-4
    assert float(jnp.max(jnp.abs(s - want_s))) <= 1e-4


def test_the_prefill_continues_from_a_state_as_the_steps_do():
    dt, c, b, cm, a, d, s0 = _scan_inputs(t=48)
    cut = lambda x, lo, hi: x[:, lo:hi]
    y1, s1 = ss.selective_scan_prefill(
        *(cut(x, 0, 16) for x in (dt, c, b, cm)), a, d, s0, route="pallas")
    y2, s2 = ss.selective_scan_prefill(
        *(cut(x, 16, 48) for x in (dt, c, b, cm)), a, d, s1, route="pallas")
    want_y, want_s = ss.selective_scan_reference(dt, c, b, cm, a, d, s0)
    assert float(jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1)
                                 - want_y))) <= 1e-4
    assert float(jnp.max(jnp.abs(s2 - want_s))) <= 1e-4


@pytest.mark.parametrize("route", ["pallas", "composition"])
def test_a_step_of_zero_moves_no_state_to_the_bit(route):
    dt, c, b, cm, a, d, s0 = _scan_inputs(t=16)
    zero = jnp.zeros_like(dt[:, 0])
    _, s = ss.selective_scan_step(zero, c[:, 0], b[:, 0], cm[:, 0], a, d,
                                  s0, route=route)
    assert bool(jnp.all(s == s0))
    # and a padded tail of a prefill: positions 10.. have dt = 0
    live = (jnp.arange(16) < 10)[None, :, None]
    _, s_pad = ss.selective_scan_prefill(jnp.where(live, dt, 0.0), c, b, cm,
                                         a, d, s0, route=route)
    _, s_true = ss.selective_scan_reference(
        *(x[:, :10] for x in (dt, c, b, cm)), a, d, s0)
    assert float(jnp.max(jnp.abs(s_pad - s_true))) <= 1e-5


def test_the_kernels_refuse_what_mosaic_cannot_tile_by_name():
    assert ss.step_kernel_refusal((64, 16, 5120)) is None
    assert ss.prefill_kernel_refusal(1024, (1, 16, 5120)) is None
    assert "128 lanes" in ss.step_kernel_refusal((4, 16, 100))
    assert "sublanes" in ss.step_kernel_refusal((4, 12, 128))
    assert "groups of 16" in ss.prefill_kernel_refusal(24, (1, 16, 128))
    assert ss.channel_tile(5120) == 1024 and ss.channel_tile(128) == 128
    assert ss.scan_block(1024) == 128 and ss.scan_block(48) == 48
    with pytest.raises(InvalidArgumentError, match="128 lanes"):
        ss.selective_scan_step(*(jnp.zeros(s) for s in (
            (2, 100), (2, 100), (2, 16), (2, 16), (16, 100), (100,),
            (2, 16, 100))), route="pallas")
    with pytest.raises(InvalidArgumentError, match="float32"):
        ss.selective_scan_step(*(jnp.zeros(s) for s in (
            (2, 128), (2, 128), (2, 16), (2, 16), (16, 128), (128,))),
            jnp.zeros((2, 16, 128), jnp.bfloat16))


# -- 2. against the reference -------------------------------------------------

@pytest.fixture(scope="module")
def weights():
    return mw.make_weights(CFG, SEED)


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = HybridMambaLM(**mw.model_kwargs(CFG))
    m.eval()
    mw.load_into(m, CFG, SEED)
    return m


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _pool(model, **kw):
    kw.setdefault("cache_layout", "paged")
    kw.setdefault("block_size", 8)
    kw.setdefault("slots", 2)
    kw.setdefault("buckets", [16, 32])
    return GenerationPool(model, max_len=64, **kw)


def _serve(model, prompts, new=8, **kw):
    pool = _pool(model, **kw)
    for i, ids in enumerate(prompts):
        pool.submit(ids, new, request_id=i)
    return pool.run(), pool


def test_the_model_has_both_kinds_of_layer_where_the_period_says(model):
    assert model.attention_layers == (False, True, False, False)
    assert [type(l.mixer).__name__ for l in model.layers] == [
        "MambaMixer", "GroupedQueryAttention", "MambaMixer", "MambaMixer"]
    mixer = model.layers[0].mixer
    assert mixer.A_log.shape == [16, 128] and mixer.conv_weight.shape \
        == [4, 128]
    assert model.layers[1].mixer.rope_theta is None


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cached_forward_agrees_with_the_reference_on_logits(model, weights,
                                                            layout):
    ids = _prompts([29])[0]
    want = ref.forward_logits(weights, ids, mw.sizes(CFG))
    full = model(pt.to_tensor(ids[None])).value[0]
    assert float(jnp.max(jnp.abs(full - want))) <= 1e-4
    cache = model.gen_decode_cache(1, 64, layout=layout, block_size=8)
    lg, cache = model(pt.to_tensor(ids[None, :17]), cache=cache)
    got = [lg.value[0]]
    for t in range(17, 29):
        lg, cache = model(pt.to_tensor(ids[None, t:t + 1]), cache=cache)
        got.append(lg.value[0])
    assert float(jnp.max(jnp.abs(jnp.concatenate(got) - want))) <= 1e-4


def test_pool_prefill_then_decode_agrees_with_the_reference(model, weights):
    # through GenerationPool: bucketed prefill (13 and 27 real positions of
    # buckets of 16 and 32), the splice of both kinds of entry, the batched
    # step.  Every served token is the reference's best at its position, or
    # within 1e-4 of it in the reference's own logits (a near tie)
    prompts = _prompts([13, 27, 9])
    got, pool = _serve(model, prompts, new=10)
    for i, prompt in enumerate(prompts):
        toks = [int(t) for t in got[i]]
        seq = np.concatenate([prompt, toks[:-1]])
        logits = ref.forward_logits(weights, seq, mw.sizes(CFG))
        rows = logits[len(prompt) - 1:]
        gap = jnp.max(rows, -1) - rows[jnp.arange(len(toks)),
                                       jnp.asarray(toks)]
        assert float(jnp.max(gap)) <= 1e-4
        assert len(set(toks)) > 2, "a model that repeats one token"
    assert pool.compile_counts()["pool_decode"] == 1


def test_the_reference_controls_move_the_logits(weights):
    ids = _prompts([40], seed=3)[0]
    sizes = mw.sizes(CFG)
    want = ref.forward_logits(weights, ids, sizes)
    fp8 = ref.forward_logits(weights, ids, sizes, "fp8")
    rounded = ref.forward_logits(weights, ids, sizes, "bf16_state", 20)
    assert float(jnp.max(jnp.abs(fp8 - want))) > 1e-2
    gap = jnp.max(jnp.abs(rounded - want), axis=-1)
    # the state is rounded from the prompt's last position on
    assert float(jnp.max(gap[:19])) == 0.0 and float(jnp.max(gap[19:])) > 0


# -- 3. the hooks on a mixed cache list ---------------------------------------

def test_the_layout_is_composed_from_the_entries(model):
    cache = model.gen_decode_cache(2, 64, layout="paged", block_size=8,
                                   per_slot=True)
    kinds = [entry_layout(c).name for c in cache]
    assert kinds == ["recurrent", "paged", "recurrent", "recurrent"]
    layout = layout_of(cache)
    assert isinstance(layout, ComposedLayout)
    assert layout.name == "paged+recurrent"
    assert (layout.paged, layout.positional, layout.spillable,
            layout.transferable, layout.recurrent) \
        == (True, False, True, False, True)
    assert "3 of the 4 cache entries" in layout.recurrent_entries()
    assert len(layout.entries(cache, "paged")) == 1
    # one kind throughout gives the registered singleton back
    assert layout_of([cache[0], cache[2]]) is get_layout("recurrent")
    assert layout_of([cache[1]]) is get_layout("paged")
    dense = layout_of(model.gen_decode_cache(1, 64, layout="dense"))
    assert dense.name == "dense+recurrent" and not dense.spillable
    assert layout.cache_dtype_str(cache) == "float32"
    assert layout.bytes_per_slot_by_kind(cache, 2, 64) == {
        "recurrent": (3, 3 * (SSM_BYTES + CONV_BYTES)),
        "paged": (1, 64 * KV_BYTES)}
    assert get_layout("recurrent").state_fields(cache[0]) == ("conv", "ssm")


@pytest.mark.parametrize("true_len", [2, 13])
def test_a_padded_bucket_leaves_the_state_of_the_true_length(model,
                                                             true_len):
    ids = _prompts([16], seed=5)[0]
    sess = DecodeSession(model, max_len=64, buckets=[16],
                         cache_layout="paged", block_size=8)
    # the session pads the prompt to its bucket of 16
    padded, _, _ = sess.prefill(ids[None, :true_len])
    exact = model.gen_decode_cache(1, 64, layout="paged", block_size=8)
    _, exact = model(pt.to_tensor(ids[None, :true_len]), cache=exact)
    for got, want in zip(padded, exact):
        if entry_layout(got).recurrent:
            assert float(jnp.max(jnp.abs(got.ssm - want.ssm))) <= 1e-5
            assert float(jnp.max(jnp.abs(got.conv - want.conv))) <= 1e-5
            assert int(got.limit) == 64
        assert int(got.index) == true_len


def test_insert_row_splices_each_entry_by_its_own_layout(model):
    pool = _pool(model, slots=3)
    layout = pool._layout
    row = model.gen_decode_cache(1, 64, layout="paged", block_size=8)
    rng = np.random.default_rng(0)
    row = [c._replace(**{f: jnp.asarray(rng.normal(size=getattr(c, f).shape),
                                        jnp.float32)
                         for f in ("conv", "ssm", "k", "v") if f in c._fields})
           for c in row]
    blocks = jnp.asarray([5, 6, 7, 0, 0, 0, 0, 0], jnp.int32)
    out = layout.insert_row(pool._cache, row, jnp.asarray(1),
                            jnp.asarray(19), blocks)
    for lay, c, r in zip(layout.layouts(out), out, row):
        assert c.index.tolist() == [0, 19, 0]
        if lay.recurrent:
            assert bool(jnp.all(c.ssm[1] == r.ssm[0]))
            assert bool(jnp.all(c.conv[1] == r.conv[0]))
            assert not bool(jnp.any(c.ssm[0])) and not bool(jnp.any(c.ssm[2]))
        else:
            assert c.table[1].tolist() == blocks.tolist()
            assert bool(jnp.all(c.k[5] == r.k[1]))
            assert bool(jnp.all(c.v[7] == r.v[3]))


def test_a_free_slots_states_come_through_the_step_untouched(model):
    # ``begin_step`` closes the free slot's update window and masks its
    # table row: its ssm, conv, K/V blocks, table and index leave
    # ``_pool_decode`` bit for bit as they entered
    pool = _pool(model, slots=3)
    params, bufs = pool._session._state_vals()
    rng = np.random.default_rng(0)
    rnd = lambda a: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
    table = jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0], [3, 4, 0, 0, 0, 0, 0, 0],
                         [5, 6, 0, 0, 0, 0, 0, 0]], jnp.int32)
    cache = []
    for lay, c in zip(pool._layout.layouts(pool._cache), pool._cache):
        idx = jnp.asarray([5, 7, 9], jnp.int32)
        cache.append(c._replace(conv=rnd(c.conv), ssm=rnd(c.ssm), index=idx)
                     if lay.recurrent else
                     c._replace(k=rnd(c.k), v=rnd(c.v), table=table,
                                index=idx))
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
            assert bool(jnp.all(c.ssm[1] == old.ssm[1]))
            assert bool(jnp.all(c.conv[1] == old.conv[1]))
            assert not bool(jnp.all(c.ssm[0] == old.ssm[0]))
            assert bool(jnp.all(c.conv[0, :2 * 128] == old.conv[0, 128:]))
            assert c.limit.shape == () and int(c.limit) == 64
        else:
            assert bool(jnp.all(c.table == table))
            for b in (3, 4):        # the free slot's own blocks
                assert bool(jnp.all(c.k[b] == old.k[b]))
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


def test_preempt_and_resume_carry_both_kinds(model):
    prompts = _prompts([5, 19, 11], seed=2)
    want, ref_pool = _serve(model, prompts, new=12)
    counts = ref_pool.compile_counts()
    pool = _pool(model)
    for i, ids in enumerate(prompts):
        pool.submit(ids, 12, request_id=i)
    for _ in range(4):
        pool.step()
    victim = next(st.rid for st in pool._active.values())
    assert pool.can_preempt(victim)
    info = pool.preempt(victim)
    assert info["state_bytes"] == 3 * (SSM_BYTES + CONV_BYTES)
    assert info["blocks_spilled"] >= 1
    assert info["spill_bytes"] == info["state_bytes"] \
        + info["blocks_spilled"] * 8 * KV_BYTES
    resumed = []
    pool.on_resume = lambda rid, i: resumed.append((rid, i))
    got = pool.run()
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    assert pool.compile_counts() == counts
    stats = pool.spill_stats()
    assert stats["preempts_total"] == 1 and stats["resumes_total"] == 1
    assert resumed[0][0] == victim
    assert resumed[0][1]["state_bytes"] == info["state_bytes"]
    # the allocator's partition is whole again
    cs = pool.cache_stats()
    assert cs["free_blocks"] == cs["num_blocks"] - 1


# -- 4. refusals, launch-ahead, spans and stats -------------------------------

REFUSED = {
    "prefix_sharing": (dict(prefix_sharing=True, prefill_chunk_tokens=8),
                       "prefill_chunk_tokens.*3 of the 4 cache entries"),
    "prefix_sharing_alone": (dict(prefix_sharing=True),
                             "prefix_sharing.*paged\\+recurrent.*3 of the 4"),
    "chunked_prefill": (dict(prefill_chunk_tokens=8),
                        "prefill_chunk_tokens.*paged\\+recurrent.*3 of the 4"),
    "disk_spill": (dict(spill_tier="disk", spill_dir="unused"),
                   "spill_tier='disk'.*paged\\+recurrent.*3 of the 4"),
    "dense_spill": (dict(cache_layout="dense", spill_tier="disk",
                         spill_dir="unused"), "no spill granularity"),
    "recurrent_layout": (dict(cache_layout="recurrent"),
                         "HybridMambaLM supports cache_layouts"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_needs_positions_everywhere_is_refused_by_name(model, feature,
                                                            tmp_path):
    kw, match = REFUSED[feature]
    if "spill_dir" in kw:
        kw = dict(kw, spill_dir=str(tmp_path))
    with pytest.raises(InvalidArgumentError, match=match):
        _pool(model, **kw)


def test_speculative_pools_and_sessions_name_the_recurrent_layers(model):
    from paddle_tpu.jit.speculative import SpeculativeDecodeSession

    draft = TransformerLM(vocab_size=97, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=64,
                          max_position=64, causal=True, dropout=0.0)
    with pytest.raises(InvalidArgumentError,
                       match="speculative.*paged\\+recurrent.*3 of the 4"):
        SpeculativePool(model, draft, max_len=64, cache_layout="paged",
                        block_size=8, buckets=[16])
    with pytest.raises(InvalidArgumentError,
                       match="speculative.*3 of the 4 cache entries"):
        SpeculativeDecodeSession(model, draft, max_len=64, buckets=[16])
    with pytest.raises(InvalidArgumentError, match="recurrent state"):
        model.gen_decode_cache(1, 64, layout="recurrent")
    pool = _pool(model)
    assert not pool.can_preempt("nobody")
    with pytest.raises(InvalidArgumentError, match="prefill_only"):
        _pool(model, prefill_only=True)


def test_no_retrace_over_joins_and_leaves_and_the_step_runs_ahead(model):
    from paddle_tpu.serving import trace as engine_trace

    tracer = engine_trace.Tracer(capacity=4096)
    engine_trace.install(tracer)
    try:
        pool = _pool(model, slots=3)
        assert pool._depth == 1
        prompts = _prompts([13, 27, 4, 9, 30, 16], seed=6)
        for i, ids in enumerate(prompts):
            pool.submit(ids, 3 + 2 * i, request_id=i)
        pool.step()
        pool.step()
        assert pool._flights, "no step in flight after a tick"
        pool.run()
    finally:
        engine_trace.uninstall()
    assert pool.compile_counts() == {"prefill": 2, "decode": 0,
                                     "pool_decode": 1, "slot_insert": 1}
    spans = [(e.name, e.meta or {}) for e in tracer.recorder.snapshot()]
    decodes = [m for n, m in spans if n == "tick.decode"]
    per_slot = 3 * (SSM_BYTES + CONV_BYTES)
    assert decodes and all(
        m["state_bytes"] == m["live"] * per_slot and m["state_entries"] == 3
        and m["kv_entries"] == 1 and m["table_blocks"] == 3 * 8
        and 1 <= m["live_blocks"] <= m["live"] * 8 for m in decodes)
    assert any(m["ahead"] == 1 for m in decodes)
    prefills = {m["bucket"]: m["chunks"] for n, m in spans
                if n == "tick.prefill"}
    assert prefills == {16: 1, 32: 1}
    assert HybridMambaLM.prefill_chunks(1024) == 8


def test_cache_stats_report_each_kind(model):
    pool = _pool(model)
    stats = pool.cache_stats()
    assert stats["cache_layout"] == pool.cache_layout == "paged+recurrent"
    assert stats["cache_entries"] == {"recurrent": 3, "paged": 1}
    assert stats["bytes_per_slot"] == {
        "recurrent": 3 * (SSM_BYTES + CONV_BYTES), "paged": 64 * KV_BYTES}
    assert stats["state_bytes_per_slot"] \
        == sum(stats["bytes_per_slot"].values())
    state_total = 2 * stats["bytes_per_slot"]["recurrent"]
    assert stats["pool_bytes"] == state_total \
        + stats["num_blocks"] * 8 * KV_BYTES
    assert stats["reachable_bytes"] == state_total      # nothing mapped
    fp = pool.config_fingerprint()
    assert fp["cache_layout"] == "paged+recurrent"
    assert fp["block_size"] == 8 and fp["state_shapes"] == [[3 * 128],
                                                            [16, 128]]
    # a model of one kind keeps its singleton and gains the same keys
    plain = TransformerLM(vocab_size=97, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=64,
                          max_position=64, causal=True, dropout=0.0)
    plain.eval()
    p = GenerationPool(plain, max_len=64, slots=2, buckets=[16],
                       cache_layout="paged", block_size=8)
    assert p._layout is get_layout("paged")
    assert p.cache_stats()["cache_entries"] == {"paged": 1}


def test_served_over_http_through_the_engine(model):
    import urllib.request

    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    prompt = _prompts([14], seed=9)[0]
    want, _ = _serve(model, [prompt], new=7)
    engine = ServingEngine(model, max_len=64, slots=2, buckets=[16, 32],
                           cache_layout="paged", block_size=8)
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
        text = engine.metrics.render_prometheus().replace(".0\n", "\n")
        assert "serving_state_bytes_per_slot %d\n" % (
            3 * (SSM_BYTES + CONV_BYTES)) in text
        assert 'serving_cache_entries{layout="recurrent"} 3\n' in text
        assert 'serving_cache_entries{layout="paged"} 1\n' in text
        assert text.count("# TYPE serving_cache_entries gauge") == 1
        assert "serving_kv_free_blocks" in text
    finally:
        front.shutdown()
        engine.shutdown(drain=False)
