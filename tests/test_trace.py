"""Request-scoped tracing + the tick flight recorder (§5g).

The contracts pinned here, in order of load-bearing-ness:

1. tracing OFF is a true no-op — an uninstalled tracer's ring buffer
   stays byte-for-byte untouched by a full serving run (the static
   analysis side of the same contract — zero new hot-path findings —
   is pinned by tests/test_static_analysis.py's full-repo gate);
2. a chaos-seeded run's flight recorder RECONCILES with the recovery
   counters: injection events == the plane's log, recovery events ==
   ``serving_recoveries_total``, resubmit events ==
   ``serving_requests_recovered_total``, and every recovered request
   shows injection → recovery → byte-identical completion in ts order;
3. the Chrome export round-trips through ``json.loads`` with
   monotonically ordered events per (pid, tid) track and closed
   request timelines;
4. the ring is bounded and its overflow observable
   (``serving_trace_events_dropped_total``);
5. the request's own timeline (``lock_wait_s``, ``queue_wait_s``) is on
   its status with NO tracer installed, and a tracer adds the
   ``submit.lock_wait`` span; the tick says what it did (``live``,
   ``queued``/``admitted``/``finished``, ``bucket``) and its own work
   has phases; every span is a ``jax.profiler.TraceAnnotation`` too,
   and says how long its thread ran (``cpu_s``), the ``tick`` how often
   it was switched out (``nvcsw``, ``nivcsw``);
6. terminal trace events exist for every request after drain/shutdown
   (timelines never end mid-span);
7. every compiled step carries the module tree and the hand-placed
   scopes in its operations' ``op_name``, at unchanged compile counts;
8. the tick is written once: every kind of pool runs
   ``GenerationPool.step``, with the same phases in the same order and
   its own ``tick.decode`` meta behind a ``tick.prep`` of its own, and
   without a tracer makes no span, reads no clock and commits the same
   tokens.
"""
import json
import re
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import (NotFoundError,
                                    PreconditionNotMetError)
from paddle_tpu.inference import (BlockDiffusionPool, GenerationPool,
                                  SpeculativePool)
from paddle_tpu.models import BlockDiffusionMoELM, TransformerLM
from paddle_tpu.serving import (MetricsRegistry, RequestState,
                                ServingEngine, Supervisor, faults,
                                trace)
from paddle_tpu.serving.faults import FaultPlane, FaultSpec
from paddle_tpu.serving.trace import FlightRecorder, TraceEvent, Tracer


def _tiny_model():
    pt.seed(0)
    return TransformerLM(vocab_size=128, hidden_size=32, num_layers=1,
                         num_heads=2, intermediate_size=64,
                         max_position=256, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    # a failing test must not leak a process-global tracer (or fault
    # plane) into the next one
    yield
    trace.uninstall()
    faults.uninstall()


def _engine(model, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("slots", 2)
    kw.setdefault("buckets", [32])
    return ServingEngine(model, **kw)


def _run(eng, prompts, budget):
    streams = [eng.submit(p, budget) for p in prompts]
    while eng.pump(8):
        pass
    return [s.result(timeout_s=0) for s in streams]


def _prompts(n=3, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, (k,)).astype("int32")
            for k in (5, 9, 7, 4, 6)[:n]]


# -- 1. tracing off is a true no-op ---------------------------------------

def _counting_tracer(calls, capacity=4096):
    """A tracer whose clocks note every reading in ``calls``."""
    import itertools
    wall, cpu = itertools.count(), itertools.count()

    def clock():
        calls.append("clock")
        return float(next(wall))

    def cpu_clock():
        calls.append("cpu")
        return float(next(cpu))

    return Tracer(capacity=capacity, clock=clock, cpu_clock=cpu_clock)


def test_trace_off_buffer_untouched(model, monkeypatch):
    # built but never installed; its clocks count their readings
    calls = []
    tracer = _counting_tracer(calls, capacity=64)
    # with no tracer, submit(), pool.step() and _run_tick build no span
    # and no profiler annotation: constructing either one fails the run
    built = []
    monkeypatch.setattr(trace._Span, "__init__",
                        lambda self, *a: built.append(a))
    monkeypatch.setattr(trace, "TraceAnnotation",
                        lambda *a, **k: built.append(a))
    # nor is any thread's CPU clock or usage read
    monkeypatch.setattr(trace.time, "thread_time",
                        lambda: calls.append("thread_time") or 0.0)
    monkeypatch.setattr(trace, "thread_switches",
                        lambda: calls.append("rusage"))
    eng = _engine(model)
    streams = [eng.submit(p, 5) for p in _prompts(2)]
    while eng.pump(8):
        pass
    statuses = [s.result(timeout_s=0) for s in streams]
    assert all(st.state == RequestState.DONE for st in statuses)
    assert built == []
    assert calls == []
    # no token was stamped, no stream grew a deque for stamps
    assert all(s._stamps is None for s in streams)
    assert len(tracer.recorder) == 0
    assert tracer.recorder.total_events == 0
    assert tracer.recorder.dropped == 0
    assert trace.active() is None
    assert eng._tracer is None
    assert eng.metrics.snapshot()[
        "serving_trace_events_dropped_total"] == 0
    # and the output is what it always was: token-identical engine runs
    # need no tracer — pinned elsewhere; here we only pin the no-op


def test_trace_on_reads_its_clocks_where_the_docs_say(model):
    # the count that is 0 above, under a tracer: two readings of each
    # clock a span, one of the wall clock an instant or a stamped token,
    # two readings of the thread's usage a tick
    if trace.thread_switches() is None:
        pytest.skip("no per-thread usage on this platform")
    calls, usage = [], []
    tracer = _counting_tracer(calls)
    real = trace.resource.getrusage
    eng = _engine(model)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace.resource, "getrusage",
                   lambda who: usage.append(who) or real(who))
        with trace.tracing(tracer):
            streams = [eng.submit(p, 5) for p in _prompts(2)]
            while eng.pump(8):
                pass
    evs = tracer.recorder.snapshot()
    spans = [e for e in evs if e.dur_s is not None]
    ticks = [e for e in evs if e.name == "tick"]
    tokens = sum(len(s.result(timeout_s=0).tokens) for s in streams)
    assert tokens == 10
    assert calls.count("cpu") == 2 * len(spans)
    assert calls.count("clock") == \
        2 * len(spans) + (len(evs) - len(spans)) + tokens
    assert len(usage) == 2 * len(ticks) > 0
    # every token was stamped with its place in its stream, in order
    assert [list(s._stamps) for s in streams] == [
        [(i, t) for i, (_, t) in enumerate(s._stamps)] for s in streams]
    assert all(len(s._stamps) == 5 for s in streams)


def test_module_instant_is_noop_when_off():
    trace.instant("req.queued", rid="x")  # must not raise, nor record
    assert trace.active() is None


# -- lifecycle + phases ---------------------------------------------------

def test_lifecycle_and_phase_events(model):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=1024)
    try:
        statuses = _run(eng, _prompts(2), 5)
    finally:
        eng.stop_trace()
    assert all(st.state == RequestState.DONE for st in statuses)
    evs = tracer.recorder.snapshot()
    names = {e.name for e in evs}
    for phase in ("tick", "tick.admit", "tick.prefill", "tick.prep",
                  "tick.decode", "tick.sample", "tick.deliver",
                  "tick.govern", "tick.observe", "tick.journal"):
        assert phase in names, phase
    # per-request lifecycle in timestamp order
    for st in statuses:
        mine = [e for e in evs if e.rid == st.request_id]
        life = [e.name for e in mine if e.name.startswith("req.")]
        assert life == ["req.queued", "req.prefilling", "req.decoding",
                        "req.done"]
        ts = [e.ts for e in mine]
        assert ts == sorted(ts)
    # the lock wait rides the queued mark, as it rides the status
    queued = {e.rid: e.meta for e in evs if e.name == "req.queued"}
    for st in statuses:
        assert queued[st.request_id]["lock_wait_s"] == st.lock_wait_s >= 0
    # spans carry durations; ticks are numbered
    spans = [e for e in evs if e.dur_s is not None]
    assert spans and all(e.dur_s >= 0 for e in spans)
    assert all("deep" not in e.to_dict() for e in spans)
    ticks = [e.meta["tick"] for e in evs if e.name == "tick"]
    assert ticks == list(range(1, len(ticks) + 1))
    # the cold engine's compiles surfaced as compile events
    assert "compile" in names


# -- the request's timeline, tracer or not --------------------------------

def test_status_carries_lock_and_queue_wait_without_a_tracer(model):
    eng = _engine(model, slots=1)
    statuses = _run(eng, _prompts(3), 4)
    assert trace.active() is None and eng._tracer is None
    for st in statuses:
        assert st.state == RequestState.DONE
        assert st.lock_wait_s >= 0 and st.queue_wait_s >= 0
        # ttft_s runs from admission: the queue wait lies inside it
        assert st.queue_wait_s <= st.ttft_s <= st.total_s
    hist = eng.metrics.snapshot()["serving_submit_lock_wait_seconds"]
    assert hist["count"] == 3


def test_queue_wait_is_none_for_a_request_that_never_took_a_slot(model):
    eng = _engine(model, slots=1)
    first = eng.submit(_prompts(1)[0], 4)
    waiting = eng.submit(_prompts(2)[1], 4, request_id="never")
    assert eng.cancel("never")
    st = waiting.result(timeout_s=0)
    assert st.state == RequestState.CANCELLED
    assert st.queue_wait_s is None and st.lock_wait_s >= 0
    while eng.pump(8):
        pass
    assert first.result(timeout_s=0).queue_wait_s >= 0
    # cancel()'s own wait for the lock goes to the same histogram
    assert eng.metrics.snapshot()[
        "serving_submit_lock_wait_seconds"]["count"] == 3


@pytest.mark.parametrize("traced", [False, True])
def test_submit_behind_a_held_lock_reports_the_wait(model, traced):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=256) if traced else None
    held, release = threading.Event(), threading.Event()

    def hold():
        with eng._lock:
            held.set()
            release.wait(5.0)

    holder = threading.Thread(target=hold)
    holder.start()
    assert held.wait(5.0)
    # held for 80 ms from here: submit() enters within a few of them, so
    # it waits the 50 ms asserted below with room for a slow thread start
    threading.Timer(0.08, release.set).start()
    try:
        stream = eng.submit(_prompts(1)[0], 3, request_id="late")
        holder.join()
        while eng.pump(8):
            pass
    finally:
        release.set()
        if traced:
            eng.stop_trace()
    st = stream.result(timeout_s=0)
    assert st.state == RequestState.DONE and st.lock_wait_s >= 0.05
    if traced:
        waits = [e for e in tracer.recorder.snapshot()
                 if e.name == "submit.lock_wait"]
        assert [e.rid for e in waits] == ["late"]
        assert waits[0].dur_s >= 0.05
        assert abs(waits[0].dur_s - st.lock_wait_s) < 0.02


# -- the tick says what it did ---------------------------------------------

@pytest.mark.parametrize("speculative", [False, True])
def test_tick_meta_says_what_the_tick_did(model, speculative):
    kw = {}
    if speculative:
        pt.seed(1)
        kw = dict(draft_model=_tiny_model(), spec_k=3)
    eng = _engine(model, buckets=[8, 32], **kw)
    tracer = eng.start_trace(capacity=4096)
    try:
        statuses = _run(eng, _prompts(5), 6)
    finally:
        eng.stop_trace()
    assert all(st.state == RequestState.DONE for st in statuses)
    evs = tracer.recorder.snapshot()
    decode = [e.meta for e in evs if e.name == "tick.decode"]
    assert decode and all(1 <= m["live"] <= m["slots"] == 2
                          for m in decode)
    assert max(m["live"] for m in decode) == 2
    ticks = [e.meta for e in evs if e.name == "tick"]
    assert sum(m["admitted"] for m in ticks) == 5
    assert sum(m["finished"] for m in ticks) == 5
    # five submitted before the first tick on two slots: it finds five
    # queued, and the queue only drains from there
    depths = [m["queued"] for m in ticks]
    assert depths[0] == 5 and depths == sorted(depths, reverse=True)
    prefill = [e.meta for e in evs if e.name == "tick.prefill"]
    assert len(prefill) == 5
    assert all(m["bucket"] >= m["prompt_tokens"] for m in prefill)
    # prompts of 5, 9, 7, 4, 6 tokens on buckets of 8 and 32
    assert sorted(m["bucket"] for m in prefill) == [8, 8, 8, 8, 32]


def test_housekeeping_phases_lie_inside_their_tick(model):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=2048)
    try:
        _run(eng, _prompts(2), 4)
        eng.pump(1)                 # an idle tick has the phases too
    finally:
        eng.stop_trace()
    evs = tracer.recorder.snapshot()
    ticks = [(e.ts, e.ts + e.dur_s) for e in evs if e.name == "tick"]
    for phase in ("tick.govern", "tick.observe", "tick.journal"):
        mine = [e for e in evs if e.name == phase]
        assert len(mine) == len(ticks), phase
        for e in mine:
            assert any(a <= e.ts and e.ts + e.dur_s <= b
                       for a, b in ticks), phase
    # and in a tick's order: govern, the pool's phases, observe, journal
    order = [e.name for e in evs
             if e.dur_s is not None and e.name != "tick"
             and ticks[0][0] <= e.ts < ticks[0][1]]
    assert order[0] == "tick.govern" and order[-1] == "tick.journal"
    assert order.index("tick.observe") > order.index("tick.admit")


def test_span_set_adds_late_meta():
    clock, cpu = iter([1.0, 3.5]), iter([10.0, 10.75])
    tr = Tracer(capacity=4, clock=lambda: next(clock),
                cpu_clock=lambda: next(cpu))
    with tr.span("tick", tick=7) as span:
        span.set(admitted=2, finished=1)
    (ev,) = tr.recorder.snapshot()
    assert (ev.ts, ev.dur_s) == (1.0, 2.5)
    # under injected clocks exactly: 0.75 s of the 2.5 on the CPU
    assert ev.meta == {"tick": 7, "admitted": 2, "finished": 1,
                       "cpu_s": 0.75}


def test_a_span_says_how_long_its_thread_ran():
    """The arithmetic, under injected clocks (the host's scheduler and the
    grain of its thread clock have no say): ``cpu_s`` is the CPU clock's
    advance between the span's two ends, read on the thread that runs the
    span."""
    wall = iter([0.0, 0.05, 1.0, 1.05, 2.0, 2.02])
    ran = {}                    # thread -> seconds it has been on a CPU

    def cpu_clock():
        return ran.get(threading.get_ident(), 0.0)

    tr = Tracer(capacity=4, clock=lambda: next(wall), cpu_clock=cpu_clock)
    with tr.span("sleeps"):
        pass                                    # 50 ms pass, none on a CPU
    with tr.span("spins"):
        ran[threading.get_ident()] = 0.05       # all 50 ms on a CPU
    sleeps, spins = tr.recorder.snapshot()
    assert (sleeps.dur_s, sleeps.meta["cpu_s"]) == (0.05, 0.0)
    assert spins.dur_s == pytest.approx(0.05) and spins.meta["cpu_s"] == 0.05
    # the CPU clock is the calling thread's: a span on another thread does
    # not see what this one ran
    ran[threading.get_ident()] = 7.0

    def aside():
        with tr.span("aside"):
            ran[threading.get_ident()] = 0.004

    t = threading.Thread(target=aside)
    t.start()
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert tr.recorder.snapshot()[-1].meta["cpu_s"] == 0.004


def test_a_span_reads_the_threads_own_cpu_clock():
    """The real clocks, with bounds a loaded host meets (six workers share
    its cores, and its thread clock may have a grain of 10 ms): a span never
    ran longer than it lasted, and a thread that slept ran for less than
    half of its span."""
    import time
    grain = 0.011
    tr = Tracer(capacity=4)
    with tr.span("sleeps"):
        time.sleep(0.05)
    with tr.span("spins"):
        end = time.perf_counter() + 0.05
        while time.perf_counter() < end:
            pass
    sleeps, spins = tr.recorder.snapshot()
    assert sleeps.dur_s >= 0.05
    assert 0.0 <= sleeps.meta["cpu_s"] < 0.5 * sleeps.dur_s
    assert 0.0 <= spins.meta["cpu_s"] <= spins.dur_s + grain


def test_the_tick_says_how_often_its_thread_was_switched_out(
        model, monkeypatch):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=1024)
    try:
        _run(eng, _prompts(2), 4)
    finally:
        eng.stop_trace()
    ticks = [e.meta for e in tracer.recorder.snapshot()
             if e.name == "tick"]
    assert ticks
    if trace.thread_switches() is None:
        assert all("nvcsw" not in m and "nivcsw" not in m for m in ticks)
    else:
        for m in ticks:
            assert isinstance(m["nvcsw"], int) and m["nvcsw"] >= 0
            assert isinstance(m["nivcsw"], int) and m["nivcsw"] >= 0
    # where the platform counts no switches by thread the keys are
    # absent and nothing else of the tick changes
    monkeypatch.setattr(trace, "resource", None)
    assert trace.thread_switches() is None
    eng = _engine(model)
    tracer = eng.start_trace(capacity=1024)
    try:
        _run(eng, _prompts(1), 3)
    finally:
        eng.stop_trace()
    ticks = [e.meta for e in tracer.recorder.snapshot()
             if e.name == "tick"]
    assert ticks and all(set(m) == {"tick", "queued", "admitted",
                                    "finished", "cpu_s"} for m in ticks)


def test_every_span_is_a_profiler_annotation_too(model, monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **stats):
            self.entry = [name, dict(stats), "built"]
            seen.append(self.entry)

        def set_metadata(self, **stats):
            self.entry[1].update(stats)

        def __enter__(self):
            self.entry[2] = "open"

        def __exit__(self, *exc):
            self.entry[2] = "closed"

    monkeypatch.setattr(trace, "TraceAnnotation", Annotation)
    eng = _engine(model)
    tracer = eng.start_trace(capacity=1024)
    try:
        eng.submit(_prompts(1)[0], 3, request_id="r0")
        while eng.pump(8):
            pass
    finally:
        eng.stop_trace()
    spans = [e for e in tracer.recorder.snapshot() if e.dur_s is not None]
    assert [n for n, _, _ in seen] == [e.name for e in sorted(
        spans, key=lambda e: e.ts)]
    assert all(state == "closed" for _, _, state in seen)
    by_name = {}
    for name, stats, _ in seen:
        by_name.setdefault(name, stats)
    # every annotation's stats end with what its span's meta ends with:
    # the thread's CPU time, and on the tick its switches
    cpu = {name: stats.pop("cpu_s") for name, stats in by_name.items()}
    assert all(isinstance(v, float) and v >= 0.0 for v in cpu.values())
    switches = {k: by_name["tick"].pop(k) for k in ("nvcsw", "nivcsw")
                if trace.thread_switches() is not None}
    assert all(isinstance(v, int) for v in switches.values())
    assert by_name["submit.lock_wait"] == {"rid": "r0"}
    assert by_name["tick.prep"] == {"rows": 1, "uploaded": 1}
    assert by_name["tick.decode"] == {"live": 1, "slots": 2, "greedy": 1,
                                      "ahead": 0}
    # one call into the engine for the download's tokens (docs 5t)
    assert by_name["tick.deliver"] == {"rows": 1, "ended": 0,
                                       "hook_calls": 1}
    assert by_name["tick.observe"] == {"refreshed": 1}
    assert by_name["tick.prefill"] == {"rid": "r0", "prompt_tokens": 5,
                                       "bucket": 32}
    assert by_name["tick"] == {"tick": 1, "queued": 1, "admitted": 1,
                               "finished": 0}


# -- scope names in the compiled steps ---------------------------------------

def _two_layer(causal):
    pt.seed(0)
    return TransformerLM(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, intermediate_size=64,
                         max_position=64, causal=causal, dropout=0.0)


def _scopes(text):
    """The ``op_name`` of every operation in a compiled or lowered
    step's text, less its last part (the primitive)."""
    found = re.findall(r'op_name="([^"]*)"', text) \
        or re.findall(r'loc\("([^"]*)"', text)
    return {n.rsplit("/", 1)[0] for n in found if "/" in n}


def test_pool_decode_carries_the_module_tree_and_hand_scopes():
    from paddle_tpu.inference import GenerationPool
    pool = GenerationPool(_two_layer(True), max_len=32, slots=2,
                          cache_layout="paged", block_size=8,
                          num_blocks=16, buckets=[8])
    pool.submit(np.arange(5, dtype=np.int32), 4)
    pool.run()
    # metadata only: the compile counts are what they were
    assert pool.compile_counts() == {"prefill": 1, "decode": 0,
                                     "pool_decode": 1, "slot_insert": 1}
    (exe,) = pool._decode_jit._exes.values()
    scopes = _scopes(exe.as_text())
    for want in ("jit(_pool_decode)/lm_head", "jit(_pool_decode)/sample",
                 "jit(_pool_decode)/encoder/layers/1/self_attn/q_proj",
                 "jit(_pool_decode)/encoder/layers/0/linear2",
                 "jit(_pool_decode)/final_norm"):
        assert want in scopes, want
    assert any(s.startswith("jit(_pool_decode)/cache_freeze")
               for s in scopes)
    (pre,) = pool._session._prefill_jit._exes.values()
    assert "jit(_prefill)/sample" in _scopes(pre.as_text())


def _window_lm():
    from paddle_tpu.models import WindowMoELM

    pt.seed(0)
    model = WindowMoELM(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, num_kv_heads=1, head_dim=16,
                        expert_size=16, num_experts=4, top_k=2, window=8,
                        sliding_window_layout=[0, 1], rope_layout=[0, 1],
                        dtype="float32")
    model.eval()
    return model


def test_a_window_models_steps_carry_its_scopes_router_first():
    """The scopes a window / global expert model adds: the router's product
    under ``moe/router`` BEFORE ``self_attn`` in a layer's program text,
    ``paged_attn`` with a child ``window`` for a window layer's call (the
    kernel's route), ``rope`` on the layer that turns, ``cache_write``,
    ``moe/experts``, ``lm_head``; in the prefill ``prefill_attn/band``."""
    from paddle_tpu.inference import GenerationPool
    pool = GenerationPool(_window_lm(), max_len=32, slots=2,
                          cache_layout="paged", block_size=4,
                          num_blocks=17, buckets=[16], route="pallas",
                          cache_dtype="float32")
    pool.submit(np.arange(11, dtype=np.int32), 4)
    pool.run()
    (exe,) = pool._decode_jit._exes.values()
    text = exe.as_text()
    scopes = _scopes(text)
    step = "jit(_pool_decode)/layers/%d/"
    for want in (step % 0 + "moe/router", step % 1 + "moe/router",
                 step % 0 + "self_attn/paged_attn",
                 step % 1 + "self_attn/paged_attn/window",
                 step % 1 + "self_attn/rope",
                 step % 1 + "self_attn/cache_write",
                 step % 1 + "moe/experts", "jit(_pool_decode)/lm_head"):
        assert any(s.startswith(want) for s in scopes), want
    assert not any(s.startswith(step % 0 + "self_attn/paged_attn/window")
                   or s.startswith(step % 0 + "self_attn/rope")
                   for s in scopes)
    (pre,) = pool._session._prefill_jit._exes.values()
    pre_scopes = _scopes(pre.as_text())
    # (a global layer's prompt is ``prefill_attn/causal`` where the flash
    # kernel runs: tests/test_tpu_compile.py; here it attends its cache)
    assert any(s.startswith("jit(_prefill)/layers/1/self_attn/"
                            "prefill_attn/band") for s in pre_scopes)
    # the router reads the layer's input: in a layer's program its
    # product stands before the attention's first operation
    import jax

    layer = pool._model.layers[1]
    text = jax.jit(lambda x: layer(pt.to_tensor(x)).value).lower(
        np.zeros((1, 6, 32), np.float32)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    order = [names.get(m) for m in re.findall(r"loc\((#loc\d+)\)$", text,
                                              re.M)]
    order = [n for n in order if n]
    first = lambda part: next(i for i, n in enumerate(order) if part in n)
    assert first("moe/router/dot_general") < first("self_attn/") \
        < first("moe/experts")


def test_train_step_carries_loss_optimizer_and_layer_scopes():
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import TransformerLMCriterion
    model = _two_layer(False)
    crit = TransformerLMCriterion(shift_labels=False)
    opt = pt.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda m, ids, lab: crit(m(ids), lab), opt)
    lowered = []
    jitted = step._jitted

    def spy(*args):
        lowered.append(jitted.lower(*args).as_text(debug_info=True))
        return jitted(*args)

    step._jitted = spy
    ids = pt.to_tensor(np.arange(16, dtype=np.int32).reshape(2, 8) % 64)
    step(ids, ids)
    step(ids, ids)
    assert jitted._cache_size() == 1            # one compile, as before
    scopes = _scopes(lowered[0])
    for want in ("jit(_step)/optimizer", "jit(_step)/jvp(loss)",
                 "jit(_step)/jvp(loss)/lm_head",
                 "jit(_step)/jvp(loss)/encoder/layers/1/linear1",
                 # backward operations keep their forward scope
                 "jit(_step)/transpose(jvp(loss))/lm_head",
                 "jit(_step)/transpose(jvp(loss))/encoder/layers/0/"
                 "self_attn/k_proj"):
        assert want in scopes, want


def test_iterated_containers_pass_their_scope_to_their_members():
    from paddle_tpu import nn

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            blocks = nn.LayerList([nn.Linear(2, 2)])
            blocks.append(nn.Linear(2, 2))      # before it has a name
            self.blocks = blocks
            self.blocks.append(nn.Linear(2, 2))     # and after
            self.head = nn.Sequential(nn.Linear(2, 2))

    net = Net()
    assert net._scope is None                   # a root has no scope
    assert [b._scope for b in net.blocks] == ["blocks/0", "blocks/1",
                                              "blocks/2"]
    # a Sequential is called, so it opens its own scope around "0"
    assert (net.head._scope, net.head[0]._scope) == ("head", "0")


# -- ring bounds + drop observability -------------------------------------

def test_ring_bounded_and_drops_counted(model):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=8)
    try:
        _run(eng, _prompts(3), 6)
    finally:
        eng.stop_trace()
    rec = tracer.recorder
    assert len(rec) <= 8
    assert rec.dropped > 0
    assert rec.total_events == len(rec) + rec.dropped
    # the engine mirrors ring overflow into the metrics registry (the
    # last accounting pass runs at the final tick, after the last span)
    assert eng.metrics.snapshot()[
        "serving_trace_events_dropped_total"] == rec.dropped
    # the recorder keeps the NEWEST events (flight-recorder semantics):
    # the oldest retained event was recorded after `dropped` others
    assert len(rec.snapshot()) == len(rec)


def test_recorder_validates_capacity():
    from paddle_tpu.core.errors import InvalidArgumentError

    with pytest.raises(InvalidArgumentError, match="capacity"):
        FlightRecorder(0)


def test_install_refuses_stacking():
    t = Tracer()
    with trace.tracing(t):
        with pytest.raises(PreconditionNotMetError, match="already"):
            trace.install(Tracer())
    assert trace.active() is None  # context manager always uninstalls


def test_stop_trace_refuses_to_kill_another_engines_tracer(model):
    eng1 = _engine(model)
    eng2 = _engine(model)
    # eng2 had its own (finished) trace session: its last-tracer
    # reference survives stop_trace for export
    eng2.start_trace()
    eng2.stop_trace()
    t1 = eng1.start_trace()
    try:
        # eng2's teardown must not silently kill eng1's live tracing
        with pytest.raises(PreconditionNotMetError, match="not this"):
            eng2.stop_trace()
        assert trace.active() is t1  # eng1's tracing survived
        # an engine that NEVER traced refuses too (its _tracer is None)
        eng3 = _engine(model)
        with pytest.raises(PreconditionNotMetError, match="not this"):
            eng3.stop_trace()
        assert trace.active() is t1
    finally:
        assert eng1.stop_trace() is t1
    assert trace.active() is None
    assert eng1.stop_trace() is None  # idempotent once nothing is on


def test_speculative_engine_gets_phase_spans(model):
    pt.seed(1)
    draft = _tiny_model()
    eng = ServingEngine(model, max_len=64, slots=2, buckets=[32],
                        draft_model=draft, spec_k=3)
    tracer = eng.start_trace(capacity=2048)
    try:
        statuses = _run(eng, _prompts(2), 6)
    finally:
        eng.stop_trace()
    assert all(st.state == RequestState.DONE for st in statuses)
    names = {e.name for e in tracer.recorder.snapshot()}
    for phase in ("tick", "tick.admit", "tick.prefill", "tick.decode",
                  "tick.sample", "tick.deliver"):
        assert phase in names, phase
    decode = [e for e in tracer.recorder.snapshot()
              if e.name == "tick.decode"]
    assert decode and all(e.meta["spec_k"] == 3 for e in decode)


# -- chrome export --------------------------------------------------------

def test_chrome_export_roundtrip_and_track_ordering(model):
    eng = _engine(model, cache_layout="paged", block_size=8)
    eng.start_trace(capacity=2048)
    try:
        statuses = _run(eng, _prompts(3), 5)
    finally:
        eng.stop_trace()
    js = eng.export_chrome_trace()
    d = json.loads(js)  # round-trips
    evs = d["traceEvents"]
    assert d["displayTimeUnit"] == "ms"
    # monotonically ordered per (pid, tid) track
    per_track = {}
    for e in evs:
        if "ts" in e:
            per_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    assert per_track
    for ts in per_track.values():
        assert ts == sorted(ts)
    # one request track per request, lifecycle spans closed by the
    # terminal instant (no open spans after a full drain)
    req_threads = [e for e in evs if e.get("ph") == "M"
                   and e["name"] == "thread_name" and e["pid"] == 1]
    assert len(req_threads) == len(statuses)
    life = [e for e in evs if e.get("cat") == "lifecycle"]
    assert not any(e.get("args", {}).get("open") for e in life)
    terminals = [e for e in life if e.get("ph") == "i"]
    assert len(terminals) == len(statuses)
    assert all(e["name"] == "DONE" for e in terminals)
    # phase tracks exist on pid 0
    phase_names = {e["name"] for e in evs if e.get("cat") == "phase"}
    assert {"tick", "tick.decode"} <= phase_names


def test_export_writes_path(model, tmp_path):
    eng = _engine(model)
    eng.start_trace()
    try:
        _run(eng, _prompts(1), 3)
    finally:
        eng.stop_trace()
    p = str(tmp_path / "trace.json")
    js = eng.export_chrome_trace(path=p)
    with open(p) as f:
        assert json.load(f) == json.loads(js)


def test_export_without_tracer_is_typed(model):
    eng = _engine(model)
    with pytest.raises(PreconditionNotMetError, match="start_trace"):
        eng.export_chrome_trace()
    with pytest.raises(PreconditionNotMetError):
        eng.flight_recorder()


def test_request_trace_lookup_and_404(model):
    eng = _engine(model)
    eng.start_trace()
    try:
        _run(eng, [_prompts(1)[0]], 3)  # auto rid 0
    finally:
        eng.stop_trace()
    tl = eng.request_trace(0)
    assert tl["request_id"] == 0
    assert [e["name"] for e in tl["events"]][-1] == "req.done"
    # string form matches too (HTTP query params arrive as strings)
    assert eng.request_trace("0")["events"] == tl["events"]
    with pytest.raises(NotFoundError, match="nope"):
        eng.request_trace("nope")


# -- 2. chaos reconciliation (the §5g acceptance criterion) ---------------

CHAOS_POINTS = ("pool.step", "pool.alloc_blocks", "stream.deliver")


def _chaos_engine(model):
    return ServingEngine(model, max_len=64, slots=2, buckets=[32],
                         cache_layout="paged", block_size=8,
                         max_retries=8)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_chaos_flight_recorder_reconciles(model, seed):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 9, 7, 4)]

    clean = _chaos_engine(model)
    want = {st.request_id: st.tokens
            for st in _run(clean, prompts, 6)}

    eng = _chaos_engine(model)
    tracer = eng.start_trace(capacity=4096)
    plane = FaultPlane(chaos_seed=seed, chaos_p=0.08,
                       chaos_points=CHAOS_POINTS, max_faults=6)
    try:
        with faults.injected(plane):
            statuses = _run(eng, prompts, 6)
    finally:
        eng.stop_trace()
    evs = tracer.recorder.snapshot()
    snap = eng.metrics.snapshot()

    # every request survived byte-identical (transient-only chaos under
    # a retry budget larger than the fault cap)
    for st in statuses:
        assert st.state == RequestState.DONE, (seed, st.state, st.error)
        np.testing.assert_array_equal(st.tokens, want[st.request_id])

    # the recorder reconciles EXACTLY with the plane and the counters
    injected = [e for e in evs if e.name == "fault.injected"]
    assert len(injected) == plane.fault_count
    assert [(e.meta["point"], e.meta["hit"], e.meta["error"])
            for e in injected] == list(plane.injected)
    recoveries = [e for e in evs if e.name == "recovery"]
    assert len(recoveries) == snap["serving_recoveries_total"]
    resubmits = [e for e in evs if e.name == "recovery.resubmit"]
    assert len(resubmits) == snap["serving_requests_recovered_total"]

    # every recovered request: injection -> recovery -> completion in
    # timestamp order, and the chrome export round-trips ordered
    for ev in resubmits:
        inj_before = [i for i in injected if i.ts <= ev.ts]
        assert inj_before, "resubmit with no prior injection event"
        done = [e for e in evs
                if e.rid == ev.rid and e.name == "req.done"]
        assert done and done[-1].ts >= ev.ts
    d = json.loads(eng.export_chrome_trace())
    per_track = {}
    for e in d["traceEvents"]:
        if "ts" in e:
            per_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for ts in per_track.values():
        assert ts == sorted(ts)


# -- supervision post-mortem dumps ----------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_stall_dumps_flight_recorder_into_health(model):
    clock = _FakeClock()
    eng = _engine(model, clock=clock)
    sup = Supervisor(eng, stall_timeout_s=0.5, clock=clock)
    eng.start_trace(capacity=256)
    try:
        _run(eng, _prompts(1), 3)
        assert eng.health()["flight_dump"] is None  # healthy: no dump
        clock.advance(0.001)  # the wedged tick starts AFTER the last
        eng._health.note_tick_start(clock())  # finished one (a wedge)
        clock.advance(1.0)
        assert sup.check_once() == ["stall-detected"]
    finally:
        eng.stop_trace()
    h = eng.health()
    dump = h["flight_dump"]
    assert dump is not None and dump["reason"] == "stall-detected"
    assert dump["events"], "post-mortem must ship its timeline"
    # 'at' is engine-clock (the injected FakeClock); the events' ts are
    # tracer-clock — trace_now is the alignment stamp across the two
    assert dump["at"] == clock()
    assert dump["trace_now"] >= max(e["ts"] for e in dump["events"])
    names = [e["name"] for e in dump["events"]]
    assert "tick" in names
    json.dumps(h)  # the whole healthz body stays JSON-serializable
    # a "stall" trace event was recorded too
    assert any(e.name == "stall"
               for e in eng._tracer.recorder.snapshot())


def test_stall_without_tracer_dumps_nothing(model):
    clock = _FakeClock()
    eng = _engine(model, clock=clock)
    sup = Supervisor(eng, stall_timeout_s=0.5, clock=clock)
    eng._health.note_tick_start(clock())
    clock.advance(1.0)
    assert sup.check_once() == ["stall-detected"]
    assert eng.health()["flight_dump"] is None


# -- 6. drain/shutdown close every timeline -------------------------------

def test_shutdown_cancel_emits_terminal_events(model):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=1024)
    try:
        streams = [eng.submit(p, 20) for p in _prompts(2)]
        eng.pump(2)  # mid-generation
        eng.shutdown(drain=False)
    finally:
        eng.stop_trace()
    evs = tracer.recorder.snapshot()
    for s in streams:
        terminal = [e for e in evs if e.rid == s.request_id
                    and e.name in trace.TERMINAL_EVENTS]
        assert terminal, "shutdown left a request timeline open"
        assert terminal[-1].name == "req.cancelled"
    d = json.loads(eng.export_chrome_trace())
    life = [e for e in d["traceEvents"] if e.get("cat") == "lifecycle"]
    assert life and not any(e.get("args", {}).get("open") for e in life)


def test_drain_emits_terminal_events(model):
    eng = _engine(model)
    tracer = eng.start_trace(capacity=1024)
    try:
        streams = [eng.submit(p, 4) for p in _prompts(2)]
        assert eng.drain() is True
    finally:
        eng.stop_trace()
    evs = tracer.recorder.snapshot()
    for s in streams:
        assert any(e.rid == s.request_id and e.name == "req.done"
                   for e in evs)


# -- satellites: metrics reset, shed/expiry events ------------------------

def test_metrics_reset_all():
    m = MetricsRegistry()
    c = m.counter("c_total", "x")
    g = m.gauge("g", "x")
    h = m.histogram("h_seconds", "x", buckets=(0.1, 1.0))
    c.inc(3)
    g.set(7.5)
    h.observe(0.05)
    h.observe(2.0)
    m.reset_all()
    snap = m.snapshot()
    assert snap["c_total"] == 0.0 and snap["g"] == 0.0
    assert snap["h_seconds"]["count"] == 0
    assert snap["h_seconds"]["sum"] == 0.0
    # registrations + identities survive (the engine holds references)
    assert m.counter("c_total") is c
    assert m.histogram("h_seconds", buckets=(0.1, 1.0)) is h
    c.inc()
    assert m.snapshot()["c_total"] == 1.0


def test_shed_and_expiry_events(model):
    from paddle_tpu.serving import DeadlineUnattainableError

    clock = _FakeClock()
    eng = _engine(model, max_len=128, slots=1, clock=clock,
                  buckets=[32])
    tracer = eng.start_trace(capacity=1024)
    try:
        # warm the tick-rate observation, then pile a backlog.  The
        # long request's deadline is generous enough to pass the
        # feasibility estimate (which runs on REAL observed tick time)
        # while the injected deadline clock controls its expiry.
        _run(eng, _prompts(1), 3)
        eng.submit(_prompts(1)[0], 100, request_id="long",
                   deadline_s=1e6)
        eng.pump(2)
        with pytest.raises(DeadlineUnattainableError):
            eng.submit(_prompts(1)[0], 20, deadline_s=1e-9)
        clock.advance(2e6)  # the long request expires
        eng.pump(1)
    finally:
        eng.stop_trace()
        eng.shutdown(drain=False)
    evs = tracer.recorder.snapshot()
    assert any(e.name == "shed" for e in evs)
    assert any(e.rid == "long" and e.name == "req.expired"
               for e in evs)


def test_recorder_tail_dicts_bounded():
    rec = FlightRecorder(capacity=100)
    for i in range(50):
        rec.append(TraceEvent(float(i), "e%d" % i))
    tail = rec.tail_dicts(10)
    assert len(tail) == 10
    assert tail[-1]["name"] == "e49"
    json.dumps(tail)


# -- 8. one tick for every kind of pool -------------------------------------

# ``tick.decode``'s meta by kind of pool (paged, so the block counts ride)
_DECODE_META = {
    "plain": {"live", "slots", "greedy", "ahead", "live_blocks",
              "table_blocks", "kv_entries", "kv_planes", "kv_write"},
    # a model with window entries (PR 50): the ring's figures beside the
    # block table's, and its expert layers'
    "window": {"live", "slots", "greedy", "ahead", "live_blocks",
               "table_blocks", "kv_entries", "kv_planes", "kv_write",
               "window_entries", "window", "ring_blocks",
               "window_live_blocks", "moe_route", "experts_held",
               "experts_read_expected"},
    "speculative": {"spec_k", "live", "slots", "ahead"},
    "block": {"live", "slots", "ahead", "rows", "store", "stores_carried",
              "committed", "tokens_per_forward", "live_blocks",
              "table_blocks", "kv_entries", "kv_planes", "kv_write",
              # its model has an expert layer (PR 45)
              "moe_route", "experts_held", "experts_read_expected"},
}
_KINDS = sorted(_DECODE_META)


def _pool_of(kind, model):
    kw = dict(slots=2, buckets=[32], cache_layout="paged", block_size=8)
    if kind == "plain":
        return GenerationPool(model, 64, **kw)
    if kind == "window":
        return GenerationPool(_window_lm(), 64, cache_dtype="float32",
                              **kw)
    if kind == "speculative":
        return SpeculativePool(model, model, 64, spec_k=2, **kw)
    pt.seed(0)
    return BlockDiffusionPool(BlockDiffusionMoELM(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, head_dim=16, expert_size=16, num_experts=4,
        top_k=2, block_length=4, mask_token_id=127, denoise_steps=2,
        dtype="float32"), 64, cache_dtype="float32", **kw)


def _drain_by_steps(pool, budget=6):
    """Three requests through two slots, one ``step()`` at a time:
    ({rid: tokens}, what each step returned beside ``_pending()`` as it
    returned)."""
    for p in _prompts(3):
        pool.submit(p, budget)
    returned = []
    while True:
        more = pool.step()
        returned.append((more, pool._pending()))
        if not more:
            break
    return {rid: t.tolist() for rid, t in pool.run().items()}, returned


@pytest.mark.parametrize("kind", _KINDS)
def test_every_pool_runs_the_one_step(model, kind):
    pool = _pool_of(kind, model)
    assert type(pool).step is GenerationPool.step
    assert type(pool)._commit is GenerationPool._commit
    assert type(pool)._pending is GenerationPool._pending
    # drained from the start: nothing pending, nothing to do
    assert pool.step() is False and pool._pending() is False
    # a request that waits for a slot is pending work all the same
    _, returned = _drain_by_steps(pool)
    assert all(more == pending for more, pending in returned)
    assert [more for more, _ in returned][-2:] == [True, False]
    assert len(returned) > 2


@pytest.mark.parametrize("kind", _KINDS)
def test_a_ticks_phases_in_order_with_the_pools_meta(model, kind):
    pool = _pool_of(kind, model)
    for p in _prompts(3):
        pool.submit(p, 6)
    tracer = trace.install(Tracer(capacity=4096))
    ticks, seen = [], 0
    more = True
    while more:
        more = pool.step()
        events = tracer.recorder.snapshot()
        ticks.append(sorted(events[seen:], key=lambda e: e.ts))
        seen = len(events)
    trace.uninstall()
    admit_phase = r"tick\.admit( tick\.prefill)*"
    settle = r"tick\.sample tick\.deliver"
    # a launch is its preparation, then its dispatch; a preparation that
    # finds no row to launch (``rows`` 0) stands alone
    launch = r"tick\.prep( tick\.decode)?"
    if type(pool)._depth:
        # the host a step behind the device: a tick that finds nothing
        # in flight admits first and launches twice; every other
        # launches step t+1, THEN downloads step t, and admits under
        # the step in flight (docs/DESIGN.md 5t)
        order = (admit_phase + "( " + launch + "){1,2}( " + settle + ")?"
                 + "|(" + launch + " )?" + settle
                 + "( " + admit_phase + ")?")
    else:
        order = admit_phase + " " + launch + "( " + settle + ")?"
    admitted, aheads, uploads = 0, [], []
    for events in ticks:
        names = " ".join(e.name for e in events)
        assert re.fullmatch(order, names), names
        admit = next(e for e in events if e.name == "tick.admit") \
            if "tick.admit" in names else None
        for e, after in zip(events, events[1:] + [None]):
            # every span says how long its thread ran
            assert 0.0 <= e.meta.pop("cpu_s") <= e.dur_s
            if e.name == "tick.prefill":
                # nested in the admit phase, one per admitted request
                assert admit.ts <= e.ts
                assert e.ts + e.dur_s <= admit.ts + admit.dur_s
                assert e.rid is not None
                assert set(e.meta) == {"prompt_tokens", "bucket"}
                assert e.meta["bucket"] == 32
                admitted += 1
            elif e.name == "tick.prep":
                # once a launch, before its dispatch and closed by then
                assert set(e.meta) == {"rows", "uploaded"}
                launched = after is not None and after.name == "tick.decode"
                assert launched == (e.meta["rows"] > 0)
                if launched:
                    assert e.ts + e.dur_s <= after.ts
                    assert e.meta["rows"] == after.meta["live"]
                    uploads.append(e.meta["uploaded"])
                else:
                    assert e.meta["uploaded"] == 0
            elif e.name == "tick.decode":
                assert set(e.meta) == _DECODE_META[kind], e.meta
                assert 1 <= e.meta["live"] <= e.meta["slots"] == 2
                aheads.append(e.meta["ahead"])
            elif e.name == "tick.deliver":
                assert set(e.meta) == {"rows", "ended", "hook_calls"}
                assert 0 <= e.meta["ended"] <= e.meta["rows"] <= 2
            else:
                assert e.meta == {}, (e.name, e.meta)
    assert admitted == 3
    assert len(uploads) == len(aheads)
    if kind == "block":
        # no row vectors there: the control table rides every launch
        assert set(uploads) == {0}
    else:
        # put on a changed row set, left standing on a repeated one
        assert uploads[0] == 1 and 0 in uploads
        assert 2 <= sum(uploads) <= 6
    # a launch that finds a step in flight says so: at depth 1 every one
    # but the first after the pool ran empty (the two requests admitted
    # together end together, the third starts over)
    assert aheads[0] == 0
    if type(pool)._depth:
        assert aheads.count(1) > aheads.count(0) == 2
    else:
        assert set(aheads) == {0}
    # the tick that finished the last request found nothing pending
    assert ticks[-1][-1].name == "tick.deliver"
    assert sum(len(t) > 1 for t in ticks) >= 3


@pytest.mark.parametrize("kind", _KINDS)
def test_untraced_tick_makes_no_span_and_the_same_tokens(
        model, kind, monkeypatch):
    with trace.tracing(Tracer(capacity=4096)):
        traced, _ = _drain_by_steps(_pool_of(kind, model))
    built = []
    monkeypatch.setattr(trace._Span, "__init__",
                        lambda self, *a: built.append(a))
    monkeypatch.setattr(trace, "TraceAnnotation",
                        lambda *a, **k: built.append(a))
    pool = _pool_of(kind, model)
    # nor is the decode span's meta built when nobody reads it
    monkeypatch.setattr(type(pool), "_decode_meta",
                        lambda self, *a: built.append(a))
    untraced, _ = _drain_by_steps(pool)
    assert built == []
    assert untraced == traced
    assert all(len(t) == 6 for t in untraced.values())
