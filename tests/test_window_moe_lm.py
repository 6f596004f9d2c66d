"""A sparse-expert decoder whose layers mix window and global attention
(``models.WindowMoELM``), its window layers served from a ring of blocks.

At small widths on the CPU, float32 (4 layers of width 64, one global then
three window layers, 4 query heads on 2 K/V heads of 16, 8 ReLU-gated
experts of 32, 2 a token, 256 rows of vocabulary; the benchmark's seeded
weights of ``toy-window-moe.json`` at ``window`` 8 and blocks of 4, so a
ring is 3 blocks and a context of 40 wraps it several times):

1. the model's forward, prefill then decode through the dense cache and
   through the ring (``insert_row`` and the decode step, logits and not
   tokens), and ``GenerationPool`` on both routes, each against the plain
   reference's full forward (``benchmark/harness/window_moe_reference.py``);
   three controls that must each differ from it;
2. the kernel under the interpreter against the composition, with and
   without a window, at a first entry that is partly masked; with no window
   the kernel's and the write kernel's programs are the parent's;
3. ``SparseExperts`` with ``relu`` and ``scores=`` on all three routes
   against the plain sum, two held halves adding up to the layer;
4. a window entry pins ``ring`` blocks a slot whatever ``max_len``, and a
   windowed walk reaches at most ``ring`` entries a row;
5. what a ring cannot carry is refused by a typed error that names it.
"""
import hashlib
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import (InvalidArgumentError,
                                    PreconditionNotMetError)
from paddle_tpu.inference import GenerationPool
from paddle_tpu.jit import DecodeSession
from paddle_tpu.jit.cache import entry_layout, layout_of
from paddle_tpu.models import WindowMoELM
from paddle_tpu.ops import pallas_decode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import window_moe_reference as ref  # noqa: E402
from harness import window_moe_weights as ww  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "toy-window-moe.json")) as _f:
    CFG = dict(json.load(_f), sliding_window_size=8)
SEED = 5
WINDOW, BS, RING = 8, 4, 3
LAYERS, KV_HEADS, HEAD_DIM = 4, 2, 16
POSITION_BYTES = 2 * KV_HEADS * HEAD_DIM * 4     # a position a layer
# two orders of float32 summation at logits of order 3
TOL = 1e-4


def _model(**changed):
    pt.seed(0)
    cfg = dict(CFG, **changed)
    m = WindowMoELM(**ww.model_kwargs(cfg))
    m.eval()
    ww.load_into(m, cfg, SEED)
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def weights():
    return ww.make_weights(CFG, SEED)


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def _reference(weights, ids, mode="float32"):
    return np.asarray(ref.forward_logits(weights, ids, ww.sizes(CFG),
                                         ww.layouts(CFG), mode))


def _pool(model, **kw):
    kw.setdefault("cache_layout", "paged")
    kw.setdefault("block_size", BS)
    kw.setdefault("slots", 3)
    kw.setdefault("buckets", [16, 32])
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("num_blocks", 31)
    return GenerationPool(model, max_len=40, **kw)


# -- 1. against the reference ---------------------------------------------------

def test_the_layers_kinds_come_from_the_two_layouts(model):
    kinds = [(l.self_attn.window, l.self_attn.rope_theta)
             for l in model.layers]
    assert kinds == [(None, None)] + [(WINDOW, 10000.0)] * 3
    assert all(l.moe.activation == "relu" and l.moe.renormalise
               and l.self_attn.q_norm is None for l in model.layers)
    with pytest.raises(InvalidArgumentError, match="rope_layout"):
        WindowMoELM(**dict(ww.model_kwargs(CFG), rope_layout=[0, 1]))
    with pytest.raises(InvalidArgumentError, match="window="):
        pt.nn.GroupedQueryAttention(64, 4, 2, 16, window=0)


def test_full_forward_agrees_with_the_reference(model, weights):
    ids = _ids(40)
    got = np.asarray(model(pt.to_tensor(ids[None])).value)[0]
    want = _reference(weights, ids)
    assert np.abs(got - want).max() < TOL and np.abs(want).max() > 1.0


def test_a_long_prompts_rows_go_through_the_experts_in_runs(model, weights,
                                                            monkeypatch):
    """More rows than ``EXPERT_ROWS`` (a long prompt: 12,288 rows at once
    did not load beside the weights on the chip) pass the experts in whole
    runs one after the other, a loop in the program, and give the logits
    the rows give at once."""
    from paddle_tpu.models import window_moe

    ids = _ids(40)
    monkeypatch.setattr(window_moe, "EXPERT_ROWS", 8)
    text = str(jax.make_jaxpr(lambda i: model(pt.to_tensor(i)).value)(
        ids[None]))
    assert text.count("scan[") >= LAYERS      # 5 runs of 8 rows a layer
    got = np.asarray(model(pt.to_tensor(ids[None])).value)[0]
    assert np.abs(got - _reference(weights, ids)).max() < TOL
    # rows that are no whole runs are taken at once
    odd = _ids(37)
    got = np.asarray(model(pt.to_tensor(odd[None])).value)[0]
    assert np.abs(got - _reference(weights, odd)).max() < TOL


@pytest.mark.parametrize("control", ["full_context", "router_post_norm",
                                     "silu_experts"])
def test_a_control_differs_from_the_reference_by_more_than_the_tolerance(
        weights, control):
    """What a program would compute that let the window layers attend
    their whole context, read the router from the post-attention norm, or
    gated the experts by ``silu``: each far outside what separates the
    program from the reference."""
    ids = _ids(40)
    gap = np.abs(_reference(weights, ids, control)
                 - _reference(weights, ids)).max()
    assert gap > 1000 * TOL, gap


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_then_decode_agrees_with_the_reference_on_logits(
        model, weights, layout):
    """A prompt of 23 (the band already cuts its first keys), then steps to
    40: a dense cache keeps every position under the banded mask; a
    self-managed paged cache hands the window layers a ring that spans the
    whole length."""
    ids = _ids(40)
    cache = model.gen_decode_cache(1, 40, "float32", layout=layout,
                                   block_size=BS)
    kinds = [entry_layout(c).name for c in cache]
    assert kinds == ([layout] * 4 if layout == "dense"
                     else ["paged"] + ["window"] * 3)
    lg, cache = model(pt.to_tensor(ids[None, :23]), cache=cache)
    outs = [np.asarray(lg.value)[0]]
    for t in range(23, 40):
        lg, cache = model(pt.to_tensor(ids[None, t:t + 1]), cache=cache)
        outs.append(np.asarray(lg.value)[0])
    assert np.abs(np.concatenate(outs) - _reference(weights, ids)).max() < TOL


def test_the_ring_carries_a_spliced_row_through_decode_steps_on_logits(
        model, weights):
    """``insert_row`` and the slot-batched step on LOGITS: two rows
    prefilled apart (23 and 9 positions: one has lapped its ring, one has
    not filled it), spliced into a pool-shaped cache whose window entries
    are rings of 3 blocks, then stepped side by side until both contexts
    have wrapped the ring several times."""
    ids = [_ids(40, seed=2), _ids(40, seed=3)]
    start = [23, 9]
    cache = model.gen_decode_cache(2, 40, "float32", per_slot=True,
                                   layout="paged", block_size=BS,
                                   num_blocks=21)
    layout = layout_of(cache)
    assert layout.name == "paged+window" and layout.windowed
    assert [c.table.shape[1] for c in cache] == [10, RING, RING, RING]
    assert [c.k.shape[0] for c in cache] == [21] + [1 + 2 * RING] * 3
    blocks = [jnp.arange(1, 11), jnp.arange(11, 21)]
    firsts = []
    for slot in range(2):
        row = model.gen_decode_cache(1, 40, "float32", layout="paged",
                                     block_size=BS)
        lg, row = model(pt.to_tensor(ids[slot][None, :start[slot]]),
                        cache=row)
        firsts.append(np.asarray(lg.value)[0, -1])
        cache = layout.insert_row(cache, row, slot, start[slot],
                                  blocks[slot])
    want = [_reference(weights, x) for x in ids]
    for slot in range(2):
        assert np.abs(firsts[slot] - want[slot][start[slot] - 1]).max() < TOL
    for step in range(17):
        toks = np.stack([ids[s][start[s] + step] for s in range(2)])
        lg, cache = model(pt.to_tensor(toks[:, None]), cache=cache)
        for s in range(2):
            assert np.abs(np.asarray(lg.value)[s, 0]
                          - want[s][start[s] + step]).max() < TOL, (step, s)
    np.testing.assert_array_equal(np.asarray(cache[1].index), [40, 26])


@pytest.mark.parametrize("route", ["auto", "pallas"])
def test_the_pool_serves_the_references_best_tokens(model, weights, route):
    """Six requests over three slots, prompts 5 to 31 and 9 tokens each, on
    the composition and on the kernel under the interpreter (with the K/V
    write kernel): every served token the reference's best."""
    prompts = [_ids(n, seed=10 + n) for n in (5, 23, 31, 17, 28, 9)]
    pool = _pool(model, route=route)
    assert pool.cache_layout == "paged+window"
    for i, p in enumerate(prompts):
        pool.submit(p, 9, request_id=i)
    out = pool.run()
    for i, p in enumerate(prompts):
        toks = np.asarray(out[i])
        rows = _reference(weights, np.concatenate([p, toks[:-1]]))[len(p) - 1:]
        gap = rows.max(-1) - rows[np.arange(len(toks)), toks]
        assert gap.max() <= TOL, (i, gap.max())
        assert len(set(toks.tolist())) > 2, "a model that repeats one token"
    # positions 12 .. 39 open a block at or past the ring's length at 12,
    # 16 ... : counted from positions, on the host
    assert pool.window_blocks_overwritten > 10


# -- 2. the kernel ----------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 8, 12])
def test_the_kernel_agrees_with_the_composition(window):
    """Grouped heads (6 on 2), blocks of 4, rows whose band starts INSIDE
    its first entry (top 13, window 8: positions 6 .. 13, entry 1 from its
    third position), at an entry's edge, before the ring is full, and a row
    that sees one key."""
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    rng = np.random.default_rng(4)
    ring = 4 if window else 8
    q = jnp.asarray(rng.normal(size=(4, 6, 1, 16)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(1 + 4 * ring, 2, 4, 16)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(1 + 4 * ring, 2, 4, 16)),
                         jnp.float32)
    table = 1 + jnp.arange(4 * ring, dtype=jnp.int32).reshape(4, ring)
    q_pos = jnp.asarray([[13], [27], [5], [0]], jnp.int32)
    kw = {} if window is None else {"window": window}
    got = fa.paged_decode_attention(q, k_pool, v_pool, table, q_pos=q_pos,
                                    route="pallas", **kw)
    want = fa.paged_decode_attention(q, k_pool, v_pool, table, q_pos=q_pos,
                                     route="composition", **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    # and both against the definition, the ring read back by position
    for b in range(4):
        top = int(q_pos[b, 0])
        lo = 0 if window is None else max(top - window + 1, 0)
        pos = np.arange(lo, top + 1)
        blk = np.asarray(table)[b, (pos // 4) % ring]
        k = np.asarray(k_pool)[blk, :, pos % 4]             # [n, Hkv, D]
        v = np.asarray(v_pool)[blk, :, pos % 4]
        for h in range(6):
            s = k[:, h // 3] @ np.asarray(q)[b, h, 0] / 4.0
            p = np.exp(s - s.max())
            plain = (p / p.sum()) @ v[:, h // 3]
            assert np.abs(np.asarray(got)[b, h, 0] - plain).max() < 1e-5


def test_a_windowed_walk_reaches_at_most_a_ring_of_entries():
    """``_window_entries`` for every top position of a long context: the
    entries from the band's first to its last, never more than ``window /
    bs + 1``, and the first entry holds the band's first position."""
    window, bs, ring = 8, 4, 3
    tops = np.arange(-1, 60, dtype=np.int32)[:, None]
    first, count = jax.vmap(
        lambda row: pallas_decode._window_entries(row[None], 0, bs, ring,
                                                  window))(tops)
    first, count = np.asarray(first), np.asarray(count)
    assert count[0] == 0 and count.max() == ring
    for top, f, n in zip(tops[1:, 0], first[1:], count[1:]):
        lo = max(top - window + 1, 0)
        assert f == lo // bs and n == top // bs - f + 1 <= ring


def _program(text):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()) \
        .hexdigest()[:16]


def test_with_no_window_the_kernels_programs_are_the_parents():
    """``_paged_call`` with ``window=None`` and the K/V write kernel trace
    to the programs of the commit before the window (PR 49): no third
    scalar, no second bound, no ``rem``.  The digests are of the jaxprs'
    text at these shapes, taken from that commit's tree with this same
    code; a change that means to move either kernel for every model takes
    new ones from its own parent."""
    table = jnp.asarray(1 + np.arange(8).reshape(2, 4), jnp.int32)
    q_pos = jnp.asarray([[13], [30]], jnp.int32)
    pool = jnp.zeros((9, 4, 16, 128), jnp.bfloat16)
    texts = {}
    for group in (1, 7):
        q = jnp.zeros((2, 4, group, 128), jnp.bfloat16)
        texts[group] = str(jax.make_jaxpr(
            lambda *a: pallas_decode._paged_call(
                *a, None, None, None, 0.125, True, group=group))(
            q, pool, pool, table, q_pos))
    # (grouped rows take ``row % lq``; one head a head has no ``rem``)
    assert " rem " not in texts[1]
    assert _program(texts[1]) == "ffd64f3405fdfb56"
    assert _program(texts[7]) == "99ba44264a876dd8"
    k_new = jnp.zeros((2, 4, 1, 128), jnp.bfloat16)
    phys = jnp.asarray([[1], [5]], jnp.int32)
    off = jnp.asarray([[3], [7]], jnp.int32)
    write = str(jax.make_jaxpr(
        lambda *a: pallas_decode.paged_kv_write_kernel(*a, interpret=True))(
        pool, pool, k_new, k_new, phys, off))
    assert _program(write) == "dfece57edd26714c"
    # the windowed call is another program: the ring's modulo is in it
    windowed = str(jax.make_jaxpr(
        lambda *a: pallas_decode._paged_call(
            *a, None, None, None, 0.125, True, window=32))(
        jnp.zeros((2, 4, 1, 128), jnp.bfloat16), pool, pool, table, q_pos))
    assert " rem " in windowed and windowed != texts[1]


def test_the_splash_prompt_agrees_with_the_banded_composition(monkeypatch):
    """``prompt_attention``'s splash kernel under the interpreter (the
    TPU's route, taken here by standing in for ``prompt_flash_supported``)
    against the composition with the band in its bias, 6 heads on 2 over
    256 positions, causal and with a window of 128."""
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 6, 256, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 128)), jnp.float32)
    attend = lambda q, k, v, window: fa.prompt_attention(
        q, k, v, 128 ** -0.5, window)
    flash = jax.jit(attend, static_argnums=3)    # traced under the stand-in
    for window in (None, 128):
        want = attend(q, k, v, window)
        with monkeypatch.context() as m:
            m.setattr(fa, "prompt_flash_supported", lambda *a: True)
            got = flash(q, k, v, window)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


# -- 3. the experts ---------------------------------------------------------------

@pytest.mark.parametrize("rows,route", [(8, "every"), (200, "grouped"),
                                        (8, "touched")])
def test_relu_experts_under_given_scores_are_the_plain_sum(rows, route,
                                                           monkeypatch):
    """``activation="relu"`` and ``routed(x, scores=)`` (the scores from
    ANOTHER tensor than the experts' input) on each of the three routes
    against a plain Python loop, and two holders of half the experts each
    adding up to the layer."""
    from paddle_tpu.nn.functional import moe

    monkeypatch.setattr(moe, "_EVERY_EXPERT_MACS", 8 * 16 * 16 * 8)
    if route == "touched":
        monkeypatch.setattr(moe, "_SKIP_COST_S", 0.0)
    h, f, e, k = 16, 8, 16, 3
    assert moe.expert_route(rows, e, e, k, h, f, 4) == route
    rng = np.random.default_rng(3)
    x = rng.normal(size=(rows, h)).astype(np.float32)
    other = rng.normal(size=(rows, h)).astype(np.float32)
    whole = pt.nn.SparseExperts(h, f, e, k, initializer_range=0.5,
                                activation="relu")
    scores = whole.scores_of(pt.to_tensor(other))
    got = np.asarray(whole.routed(pt.to_tensor(x), scores=scores).value)
    wr, wg, wu, wd = (np.asarray(p.value, np.float64) for p in (
        whole.router, whole.w_gate, whole.w_up, whole.w_down))
    logits = other.astype(np.float64) @ wr
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros((rows, h))
    for t in range(rows):
        chosen = np.argsort(-probs[t], kind="stable")[:k]
        for ex in chosen:
            want[t] += probs[t, ex] / probs[t, chosen].sum() * (
                (np.maximum(x[t] @ wg[ex], 0) * (x[t] @ wu[ex])) @ wd[ex])
    assert np.abs(got - want).max() < 2e-4 and np.abs(want).max() > 0.5
    # the layer's own scores are its input's: not these
    own = np.asarray(whole.routed(pt.to_tensor(x)).value)
    assert np.abs(own - got).max() > 0.1
    # silu is another layer
    silu = pt.nn.SparseExperts(h, f, e, k)
    for name in ("router", "w_gate", "w_up", "w_down"):
        getattr(silu, name)._replace_value(getattr(whole, name).value)
    assert np.abs(np.asarray(silu.routed(pt.to_tensor(x), scores=scores)
                             .value) - got).max() > 0.05
    parts = np.zeros_like(got)
    for first in (0, e // 2):
        share = pt.nn.SparseExperts(h, f, e, k, held=(first, e // 2),
                                    activation="relu")
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name)._replace_value(
                getattr(whole, name).value[first:first + e // 2])
        parts += np.asarray(share.routed(pt.to_tensor(x),
                                         scores=scores).value)
    assert np.abs(parts - want).max() < 2e-4
    with pytest.raises(InvalidArgumentError, match="activation"):
        pt.nn.SparseExperts(h, f, e, k, activation="gelu")


# -- 4. what a ring pins ------------------------------------------------------------

@pytest.mark.parametrize("max_len", [40, 400])
def test_a_window_entry_pins_a_ring_of_blocks_whatever_max_len(model,
                                                                max_len):
    cache = model.gen_decode_cache(2, max_len, "float32", per_slot=True,
                                   layout="paged", block_size=BS,
                                   num_blocks=9)
    by_kind = layout_of(cache).bytes_per_slot_by_kind(cache, 2, max_len)
    assert by_kind == {
        "paged": (1, -(-max_len // BS) * BS * POSITION_BYTES),
        "window": (3, 3 * RING * BS * POSITION_BYTES)}
    assert all(c.k.shape[0] == 1 + 2 * RING for c in cache[1:])
    np.testing.assert_array_equal(np.asarray(cache[1].table),
                                  [[1, 2, 3], [4, 5, 6]])
    assert int(cache[1].window) == WINDOW


def test_stats_meta_and_fingerprint_tell_the_kinds_apart(model):
    pool = _pool(model)
    stats = pool.cache_stats()
    assert stats["cache_entries"] == {"paged": 1, "window": 3}
    assert stats["bytes_per_slot"] == {
        "paged": 40 * POSITION_BYTES,
        "window": 3 * RING * BS * POSITION_BYTES}
    # the allocator's pool, and the window pools held whole
    assert stats["pool_bytes"] == 31 * BS * POSITION_BYTES \
        + 3 * (3 * RING + 1) * BS * POSITION_BYTES
    assert stats["num_blocks"] == 31
    fp = pool.config_fingerprint()
    assert (fp["window"], fp["ring"], fp["block_size"]) == (WINDOW, RING, BS)
    assert pool._blocks_needed(23, 9) == 8      # the global entries' alone
    pool.submit(_ids(23), 9, request_id="a")
    pool.submit(_ids(5), 9, request_id="b")
    from paddle_tpu.serving import trace as engine_trace

    tracer = engine_trace.Tracer(capacity=1 << 12)
    with engine_trace.tracing(tracer):
        pool.run()
    metas = [e.meta for e in tracer.recorder.snapshot()
             if e.name == "tick.decode"]
    assert metas
    for m in metas:
        assert (m["kv_entries"], m["window_entries"], m["window"],
                m["ring_blocks"]) == (1, 3, WINDOW, RING)
        assert m["table_blocks"] == 3 * 10
        assert 1 <= m["window_live_blocks"] <= m["live"] * RING
        assert m["window_live_blocks"] <= m["live_blocks"]
        assert m["moe_route"] and m["experts_held"] == 4 * 8
    assert any(m["window_live_blocks"] < m["live_blocks"] for m in metas)


def test_served_over_http_with_the_gauge_and_the_counter(model, weights):
    import urllib.request

    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    prompt = _ids(14, seed=9)
    engine = ServingEngine(model, max_len=40, slots=2, buckets=[16, 32],
                           cache_layout="paged", block_size=BS,
                           num_blocks=21, cache_dtype="float32")
    front = ServingHTTPFrontend(engine)
    engine.start()
    front.start()
    try:
        host, port = front.address
        req = urllib.request.Request(
            "http://%s:%d/generate" % (host, port),
            data=json.dumps({"prompt": prompt.tolist(),
                             "max_new_tokens": 12}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            lines = [json.loads(l) for l in resp.read().splitlines() if l]
        toks = np.asarray([l["token"] for l in lines if "token" in l])
        assert len(toks) == 12
        rows = _reference(weights, np.concatenate([prompt, toks[:-1]]))[13:]
        assert (rows.max(-1) - rows[np.arange(12), toks]).max() <= TOL
        engine.settle()
        text = engine.metrics.render_prometheus().replace(".0\n", "\n")
        assert 'serving_cache_entries{layout="window"} 3\n' in text
        assert 'serving_cache_entries{layout="paged"} 1\n' in text
        # 14 + 12 positions: blocks 3, 4, 5 and 6 each lapped a ring entry
        # (block 3 by the prompt: its first block was never copied)
        assert "serving_window_blocks_overwritten_total 4\n" in text
        assert engine.metrics.snapshot()["serving_recoveries_total"] == 0
        assert engine.cache_stats()["cache_entries"]["window"] == 3
    finally:
        front.shutdown()
        engine.shutdown(drain=False)


# -- 5. refusals ------------------------------------------------------------------------

REFUSED = {
    "prefix_sharing": (dict(prefix_sharing=True, prefill_chunk_tokens=8),
                       "prefix_sharing cannot apply.*window entry.*ring"),
    "chunked_prefill": (dict(prefill_chunk_tokens=8),
                        "prefill_chunk_tokens cannot apply.*window entry"),
    "disk_spill": (dict(spill_tier="disk", spill_dir="unused"),
                   "spill_tier='disk'.*window entry"),
    "ptkv_hand_off": (dict(prefill_only=True, spill_tier="disk",
                           spill_dir="unused"),
                      "spill_tier='disk'.*window entry"),
    "int8_pool": (dict(cache_dtype="int8"), "window entry.*int8"),
    "ragged_window": (dict(block_size=3, num_blocks=41),
                      "window=8 is not whole blocks of block_size=3"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_a_ring_cannot_carry_is_refused_by_name(model, feature,
                                                     tmp_path):
    kw, match = REFUSED[feature]
    if "spill_dir" in kw:
        kw = dict(kw, spill_dir=str(tmp_path))
    with pytest.raises(InvalidArgumentError, match=match):
        _pool(model, **kw)


def test_speculation_meshes_preemption_and_a_chunk_midway_are_refused(model):
    from paddle_tpu.inference import SpeculativePool
    from paddle_tpu.jit.mesh import DecodeMesh

    with pytest.raises(InvalidArgumentError,
                       match="speculative decoding.*window entry"):
        SpeculativePool(model, model, spec_k=2, max_len=40, slots=2,
                        buckets=[16, 32], cache_layout="paged",
                        block_size=BS, cache_dtype="float32")
    for dp, mp in ((1, 2), (2, 1)):
        with pytest.raises(InvalidArgumentError,
                           match="cannot place a model with window entries "
                                 "\\(3 layers of window=8\\)"):
            DecodeMesh(dp, mp).validate_model(model)
    DecodeMesh(1, 1).validate_model(model)
    pool = _pool(model)
    pool.submit(_ids(9), 8, request_id="a")
    pool.step()
    assert not pool.can_preempt("a")
    with pytest.raises(PreconditionNotMetError,
                       match="preempt and resume of a window entry are not "
                             "built"):
        pool.preempt("a")
    # a chunk of several positions that starts mid-way, against a ring
    cache = model.gen_decode_cache(1, 40, "float32", layout="paged",
                                   block_size=BS)
    _, cache = model(pt.to_tensor(_ids(9)[None]), cache=cache)
    with pytest.raises(InvalidArgumentError,
                       match="starts mid-way against a window entry"):
        model(pt.to_tensor(_ids(3)[None]), cache=cache)
    with pytest.raises(InvalidArgumentError, match="ONE query a row"):
        pallas_decode.paged_decode_attention_kernel(
            jnp.zeros((1, 2, 3, 16)), jnp.zeros((4, 2, 4, 16)),
            jnp.zeros((4, 2, 4, 16)), jnp.ones((1, 3), jnp.int32),
            jnp.zeros((1, 3), jnp.int32), 0.25, interpret=True, window=8)
    with pytest.raises(InvalidArgumentError, match="keeps a float K/V|int8"):
        DecodeSession(model, max_len=40, buckets=[16], cache_dtype="int8",
                      cache_layout="paged", block_size=BS)


def test_the_bench_tool_rehearses_the_windowed_call_here(capsys):
    """``tools/paged_kernel_bench.py --window``: the windowed call at the
    cell's geometry on the chip; here its control flow at toy sizes under
    the interpreter, against the composition, and no time."""
    sys.path.insert(0, ROOT)
    from tools import paged_kernel_bench as bench

    assert bench.GEOMETRIES["smallthinker"][:7] == (16, 28, 4, 1, 128, 128,
                                                    128)
    assert bench.WINDOWS == {"smallthinker": 4096}
    assert bench.main(["--cpu-toy", "--window", "--geometry", "smallthinker",
                       "gpt", "--context", "mix", "whole"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert len(lines) == 2          # gpt has no window: left out
    for line in lines:
        assert line["window"] == 16 and line["table_entries"] == 4 * 3
        assert line["live_entries"] <= 4 * 3
        assert line["max_abs_diff"] < 1e-5 and "ms_a_call" not in line
