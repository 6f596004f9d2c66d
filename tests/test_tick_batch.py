"""What the tick does for a download it does once (docs/DESIGN.md 5t).

The tokens a download delivered reach the engine in ONE call of the pool's
batch hook (``on_tokens``), and the cache gauges are recomputed only on a
tick whose allocator changed (``alloc_version``).  Neither may change what
a client, the journal or ``/metrics`` sees: pinned here against the
per-token hook (``on_token``, which a pool used alone still has) and
against ``cache_stats()`` itself, for every kind of pool.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.inference import (BlockDiffusionPool,  # noqa: E402
                                  GenerationPool, SpeculativePool)
from paddle_tpu.models import (BlockDiffusionMoELM,  # noqa: E402
                               HybridMambaLM, TransformerLM)
from paddle_tpu.serving import ServingEngine, faults  # noqa: E402
from paddle_tpu.serving import stream as stream_mod  # noqa: E402
from paddle_tpu.serving.faults import (FaultPlane, FaultSpec,  # noqa: E402
                                       TransientInjectedFault)

from harness import mamba_weights as mw  # noqa: E402

VOCAB = 96


def _lm(seed, layers=2):
    pt.seed(seed)
    m = TransformerLM(vocab_size=VOCAB, hidden_size=32, num_layers=layers,
                      num_heads=2, intermediate_size=64, max_position=64,
                      causal=True, dropout=0.0)
    m.eval()
    return m


@pytest.fixture(scope="module")
def lm():
    return _lm(5)


@pytest.fixture(scope="module")
def draft():
    return _lm(6, layers=1)


@pytest.fixture(scope="module")
def ssm():
    pt.seed(7)
    m = nn.SSMLM(vocab_size=VOCAB, hidden_size=32, num_layers=2, d_state=16,
                 dropout=0.0)
    m.eval()
    return m


@pytest.fixture(scope="module")
def block_lm():
    pt.seed(3)
    m = BlockDiffusionMoELM(
        vocab_size=VOCAB, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, head_dim=16, expert_size=16, num_experts=4,
        top_k=2, block_length=4, mask_token_id=95, denoise_steps=2,
        dtype="float32")
    m.eval()
    return m


HYBRID = dict(vocab_size=97, hidden_size=64, num_layers=4,
              attn_layer_period=4, attn_layer_offset=1,
              num_attention_heads=4, num_key_value_heads=1,
              intermediate_size=128, mamba_expand=2, mamba_d_state=16,
              mamba_d_conv=4, mamba_dt_rank=8, rms_norm_eps=1e-6,
              weights_dtype="float32",
              assumed={"dt_init": [0.001, 0.1], "initializer_std": 0.1})


@pytest.fixture(scope="module")
def hybrid():
    pt.seed(0)
    m = HybridMambaLM(**mw.model_kwargs(HYBRID))
    m.eval()
    mw.load_into(m, HYBRID, 11)
    return m


# kind -> (model fixture, pool class, pool arguments, engine arguments)
KINDS = {
    "paged": ("lm", GenerationPool,
              dict(cache_layout="paged", block_size=8), {}),
    "recurrent": ("ssm", GenerationPool, dict(cache_layout="recurrent"), {}),
    "block": ("block_lm", BlockDiffusionPool,
              dict(cache_dtype="float32"), {}),
    "speculative": ("lm", SpeculativePool,
                    dict(cache_layout="paged", block_size=8), {"spec_k": 3}),
}
GEOMETRY = dict(max_len=48, slots=4, buckets=[16, 32])
PROMPTS = (5, 9, 7, 11, 6, 10)
BUDGETS = (9, 12, 8, 6, 12, 1)


def prompts():
    rng = np.random.RandomState(31)
    return [rng.randint(0, 90, (n,)).astype("int32") for n in PROMPTS]


def standalone(request, kind):
    """The kind's pool used alone, no engine around it."""
    model, cls, kw, ekw = KINDS[kind]
    args = (request.getfixturevalue(model),)
    if cls is SpeculativePool:
        args += (request.getfixturevalue("draft"),)
    return cls(*args, GEOMETRY["max_len"], slots=GEOMETRY["slots"],
               buckets=GEOMETRY["buckets"], **dict(kw, **ekw))


def engine(request, kind, **more):
    model, cls, kw, ekw = KINDS[kind]
    if cls is SpeculativePool:
        more["draft_model"] = request.getfixturevalue("draft")
    return ServingEngine(request.getfixturevalue(model),
                         **dict(GEOMETRY, **kw, **ekw, **more))


def drain(eng, bound=400):
    n = 0
    while eng.pump(1):
        n += 1
        assert n < bound, "engine failed to drain"


def per_token_reference(request, kind):
    """{rid: tokens} as the PER-TOKEN hook of a pool used alone sees
    them, with the order the requests finished in."""
    pool = standalone(request, kind)
    seen, steps, finished = {}, [], []

    def on_token(rid, tok):
        seen.setdefault(rid, []).append(tok)
        steps.append(pool.token_commit_step)

    pool.on_token = on_token
    pool.on_finish = lambda rid, toks, why: finished.append(
        (rid, list(seen[rid])))
    for i, (p, n) in enumerate(zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    out = {rid: t.tolist() for rid, t in pool.run().items()}
    # a pool with only ``on_token`` set still gets every token, in
    # order, and a request's ``on_finish`` follows its last token
    assert seen == out
    assert sorted(finished) == sorted(out.items())
    assert [len(out[i]) for i in range(len(BUDGETS))] == list(BUDGETS)
    # the block pool's commit step stands beside each token while the
    # per-token hook runs; no other pool has one
    assert all((s is not None) == (kind == "block") for s in steps)
    return out


@pytest.fixture(scope="module")
def references():
    return {}


def reference(request, kind):
    cache = request.getfixturevalue("references")
    if kind not in cache:
        cache[kind] = per_token_reference(request, kind)
    return cache[kind]


def queued(stream):
    """What the stream was handed and nobody has read yet."""
    return list(stream._q.queue)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_stream_gets_the_per_token_hooks_tokens_then_its_end(request,
                                                              kind):
    want = reference(request, kind)
    eng = engine(request, kind)
    batches = []
    hook = eng._pool.on_tokens
    eng._pool.on_tokens = lambda batch: (batches.append(list(batch)),
                                         hook(batch))[1]
    streams = [eng.submit(p, n, request_id=i)
               for i, (p, n) in enumerate(zip(prompts(), BUDGETS))]
    drain(eng)
    for i, s in enumerate(streams):
        got = queued(s)
        # tokens in order, the terminal marker after the last of them
        assert got[-1] is stream_mod._TERMINAL
        assert got[:-1] == want[i]
        st = s.result(timeout_s=0)
        assert st.state == "DONE" and st.tokens.tolist() == want[i]
        assert (st.commit_steps is not None) == (kind == "block")
    total = sum(BUDGETS)
    assert sum(len(b) for b in batches) == total
    # with four rows live a download's tokens came in one call
    assert max(len({rid for rid, _, _ in b}) for b in batches) == 4
    assert all((step is not None) == (kind == "block")
               for b in batches for _, _, step in b)
    # one observation a token: a first token or a gap
    assert eng._h_ttft.count == len(BUDGETS)
    assert eng._h_itl.count == total - len(BUDGETS)
    snap = eng.metrics.snapshot()
    assert snap["serving_tokens_emitted_total"] == total
    assert snap["serving_requests_completed_total"] == len(BUDGETS)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_deliver_fault_at_token_k_of_a_batch(request, kind):
    """A ``stream.deliver`` fault at token k of a four-row batch: tokens
    before k delivered AND committed, k onward neither, and recovery
    regenerates token k once."""
    want = reference(request, kind)
    k = 2
    eng = engine(request, kind)
    hook, real_recover = eng._pool.on_tokens, eng._recover
    plane = FaultPlane([FaultSpec("stream.deliver",
                                  error=TransientInjectedFault,
                                  after=k, times=1)])
    armed, at_fault = [], {}

    def on_tokens(batch):
        if not armed and len({rid for rid, _, _ in batch}) == 4:
            # the first batch that holds a token of each of four rows
            cm = faults.injected(plane)
            cm.__enter__()
            armed.append((cm, list(batch),
                          {r.rid: len(r.tokens)
                           for r in eng._live.values()}))
        return hook(batch)

    def recover(exc):
        for rec in eng._live.values():
            at_fault[rec.rid] = (queued(rec.stream), list(rec.tokens))
        return real_recover(exc)

    eng._pool.on_tokens, eng._recover = on_tokens, recover
    streams = [eng.submit(p, n, request_id=i)
               for i, (p, n) in enumerate(zip(prompts(), BUDGETS))]
    try:
        drain(eng)
    finally:
        for cm, _, _ in armed:
            cm.__exit__(None, None, None)
    assert [kind_ for _, _, kind_ in plane.injected] \
        == ["TransientInjectedFault"]
    (_, batch, before), = armed
    # at the fault: every live stream holds exactly what its record
    # committed, and of the batch the k tokens before the fault
    assert at_fault
    gained = {}
    for rid, (delivered, committed) in at_fault.items():
        assert delivered == committed
        gained[rid] = committed[before[rid]:]
    assert sum(len(g) for g in gained.values()) == k
    handed = {}
    for rid, tok, _ in batch[:k]:
        handed.setdefault(rid, []).append(tok)
    assert {rid: g for rid, g in gained.items() if g} == handed
    assert eng.metrics.snapshot()["serving_recoveries_total"] == 1
    # and in the end every stream got every token once
    for i, s in enumerate(streams):
        got = queued(s)
        assert got[-1] is stream_mod._TERMINAL and got[:-1] == want[i]
        st = s.result(timeout_s=0)
        assert st.state == "DONE" and st.tokens.tolist() == want[i]
    assert eng._h_ttft.count + eng._h_itl.count == sum(BUDGETS)


def test_a_fault_behind_a_requests_last_token_leaves_it_done(request):
    """The rows of a batch are finished after the whole batch was handed
    on: a request whose LAST token went out before the fault is done
    with nothing left to resubmit, and says so."""
    want = reference(request, "paged")
    eng = engine(request, "paged")
    hook = eng._pool.on_tokens
    plane = FaultPlane([FaultSpec("stream.deliver",
                                  error=TransientInjectedFault,
                                  after=1, times=1)])
    armed = []

    def on_tokens(batch):
        rec = eng._live.get(batch[0][0])
        if not armed and len(batch) > 1 and rec is not None \
                and len(rec.tokens) == rec.max_new - 1:
            cm = faults.injected(plane)
            cm.__enter__()
            armed.append((cm, batch[0][0]))
        return hook(batch)

    eng._pool.on_tokens = on_tokens
    streams = [eng.submit(p, n, request_id=i)
               for i, (p, n) in enumerate(zip(prompts(), BUDGETS))]
    try:
        drain(eng)
    finally:
        for cm, _ in armed:
            cm.__exit__(None, None, None)
    assert armed and len(plane.injected) == 1
    for i, s in enumerate(streams):
        st = s.result(timeout_s=0)
        assert st.state == "DONE" and st.finish_reason == "length"
        assert st.tokens.tolist() == want[i]
        assert queued(s)[:-1] == want[i]
    # the one that ended in the faulted batch was never resubmitted
    snap = eng.metrics.snapshot()
    assert snap["serving_recoveries_total"] == 1
    assert snap["serving_requests_recovered_total"] \
        < len(streams) - snap["serving_requests_failed_total"]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_journal_replays_what_the_batches_delivered(request, kind,
                                                        tmp_path):
    want = reference(request, kind)
    jpath = str(tmp_path / "wal.journal")
    more = dict(journal_path=jpath)
    a = engine(request, kind, **more)
    hook = a._pool.on_tokens

    def on_tokens(batch):
        before = {rid: list(t) for rid, t in a._jl_tick_toks.items()}
        hook(batch)
        gained = {}
        for rid, tok, _ in batch:
            gained.setdefault(rid, []).append(tok)
        # the tick's journal buffer gained the batch, a row at a time
        assert {rid: t[len(before.get(rid, ())):]
                for rid, t in a._jl_tick_toks.items()
                if t[len(before.get(rid, ())):]} == gained

    a._pool.on_tokens = on_tokens
    streams = [a.submit(p, n, request_id=i)
               for i, (p, n) in enumerate(zip(prompts(), BUDGETS))]
    a.pump(5)
    delivered = {s.request_id: [t for t in queued(s)
                                if t is not stream_mod._TERMINAL]
                 for s in streams}
    live = set(a._live)
    assert live and any(delivered[rid] for rid in live)
    del a
    b = engine(request, kind, **more)
    b.restore(jpath)
    assert set(b._live) == live
    restored = {rid: rec.stream for rid, rec in b._live.items()}
    for rid, rec in b._live.items():
        # the journal held exactly what the streams were handed
        assert rec.tokens == delivered[rid]
    drain(b)
    for rid, s in restored.items():
        assert s.result(timeout_s=0).tokens.tolist() == want[rid]


# -- the cache gauges --------------------------------------------------------

def cache_gauges(eng):
    """Every gauge ``_observe_cache_gauges`` sets, by what it should read."""
    stats = eng.cache_stats()
    want = {eng._g_kv_bytes: stats["reachable_bytes"],
            eng._g_kv_resident: stats["pool_bytes"]}
    if eng._g_state_slot is not None:
        want[eng._g_state_slot] = stats["bytes_per_slot"]["recurrent"]
    for kind_, g in eng._g_cache_entries.items():
        want[g] = stats["cache_entries"][kind_]
    if eng._g_kv_free is not None:
        want[eng._g_kv_free] = stats["free_blocks"]
    if eng._g_spilled_blocks is not None:
        want[eng._g_spilled_blocks] = stats["spilled_blocks"]
    return want


CHURN = {
    # prefix sharing and chunked prefill on: blocks are shared and
    # indexed while their owner still prefills
    "shared-prefix": ("lm", dict(cache_layout="paged", block_size=8,
                                 num_blocks=40, prefix_sharing=True,
                                 prefill_chunk_tokens=8)),
    # two kinds of entry in one pool: K/V blocks and recurrent states
    "two-kinds": ("hybrid", dict(cache_layout="paged", block_size=8,
                                 num_blocks=40)),
}


@pytest.mark.parametrize("case", sorted(CHURN))
def test_cache_gauges_read_cache_stats_after_every_tick(request, case):
    model, kw = CHURN[case]
    eng = ServingEngine(request.getfixturevalue(model), max_len=64, slots=3,
                        buckets=[16, 32], **kw)
    pool = eng._pool
    refreshes = eng._c_gauge_refreshes
    rng = np.random.RandomState(2)
    head = rng.randint(0, 90, (16,)).astype("int32")

    def prompt(n):
        return np.concatenate(
            [head, rng.randint(0, 90, (n,)).astype("int32")])

    moved = {"ticks": 0, "still": 0, "version": None}

    def tick():
        count = refreshes.value
        work = eng.pump(1)
        for g, value in cache_gauges(eng).items():
            assert g.value == value, g.name
        # recomputed on the ticks that found the allocator moved (inside
        # the tick or between two), only on those
        changed = pool.alloc_version() != moved["version"]
        moved["version"] = pool.alloc_version()
        assert refreshes.value - count == int(changed)
        moved["ticks" if changed else "still"] += 1
        return work

    def tick_until(done, bound=80):
        n = 0
        while not done():
            tick()
            n += 1
            assert n < bound

    tick()                      # an idle engine's tick sets them once
    assert refreshes.value == 1
    a = eng.submit(prompt(6), 30, request_id="a")
    b = eng.submit(prompt(9), 5, request_id="b")
    tick_until(b.done)          # b ends on the way: its blocks go
    c = eng.submit(prompt(4), 30, request_id="c", priority="low")
    tick_until(lambda: eng.request_state("c") == "DECODING")
    # between two ticks: cancel, preempt; the next tick's gauges have it
    assert eng.cancel("c")
    tick()
    d = eng.submit(prompt(5), 24, request_id="d", priority="low")
    tick_until(lambda: eng.request_state("d") == "DECODING")
    if case == "shared-prefix":
        assert pool.prefix_stats()["hits"] >= 1
        assert eng.cache_stats()["shared_blocks"] >= 1
    assert eng.preempt("d") == "d"
    tick()
    tick_until(lambda: a.done() and d.done())   # d resumed on the way
    assert eng.metrics.snapshot()["serving_resumes_total"] == 1
    assert not tick()
    # a steady stretch: rows decode, nothing is admitted or ends
    e = eng.submit(prompt(3), 40, request_id="e")
    f = eng.submit(prompt(7), 40, request_id="f")
    tick_until(lambda: eng.request_state("e") == "DECODING"
               and eng.request_state("f") == "DECODING")
    tick()
    still = refreshes.value
    tracer = eng.start_trace(capacity=4096)
    try:
        for _ in range(20):
            tick()
    finally:
        eng.stop_trace()
    assert refreshes.value == still and not e.done() and not f.done()
    spans = [ev for ev in tracer.recorder.snapshot()
             if ev.dur_s is not None]
    delivers = [ev.meta for ev in spans if ev.name == "tick.deliver"]
    observes = [ev.meta for ev in spans if ev.name == "tick.observe"]
    assert len(delivers) == len(observes) == 20
    assert all(m["hook_calls"] == 1 and m["rows"] == 2 for m in delivers)
    assert all(set(m) == {"refreshed", "cpu_s"} and m["refreshed"] == 0
               for m in observes)
    while tick():
        pass
    assert e.done() and f.done()
    assert moved["ticks"] >= 8 and moved["still"] >= 30


def test_fifty_steady_decode_ticks_recompute_nothing(lm):
    eng = ServingEngine(lm, max_len=64, slots=4, buckets=[16],
                        cache_layout="paged", block_size=8)
    refreshes = eng._c_gauge_refreshes
    streams = [eng.submit(p[:4], 58, request_id=i)
               for i, p in enumerate(prompts()[:4])]
    eng.pump(4)
    seen, version = refreshes.value, eng._pool.alloc_version()
    calls = []
    stats = eng._pool.cache_stats
    eng._pool.cache_stats = lambda: (calls.append(1), stats())[1]
    for _ in range(50):
        assert eng.pump(1)
    assert refreshes.value == seen and not calls
    assert eng._pool.alloc_version() == version
    del eng._pool.cache_stats
    for g, value in cache_gauges(eng).items():
        assert g.value == value, g.name
    drain(eng)
    assert all(s.done() for s in streams) and refreshes.value > seen
