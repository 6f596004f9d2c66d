"""Serving engine: lifecycle, streaming, deadlines, metrics (§5c).

Every lifecycle test drives the engine with the synchronous ``pump()``
mode — deterministic and single-threaded (the tier-1 CPU budget forbids
concurrent load; the background thread runs the identical ``_tick``, so
the modes cannot diverge and get one slow-marked test).  The contracts:

- greedy streamed output is TOKEN-IDENTICAL to ``GenerationPool.run()``
  for the same prompts, dense and paged, still exactly two compiles;
- a deadline-expired or cancelled request frees its slot and paged
  blocks (``cache_stats()`` back to baseline) without corrupting the
  survivors;
- admission past ``max_queue`` fails fast with the typed, retryable
  ``QueueFullError``; duplicate request ids fail with the typed
  ``DuplicateRequestError`` naming the colliding id;
- ``drain()`` stops admissions and finishes in-flight requests;
- ``metrics.snapshot()`` carries the expiry/cancellation counts plus
  the TTFT and queue-depth series, and ``render_prometheus()`` emits
  well-formed text exposition.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import (InvalidArgumentError, NotFoundError,
                                    PreconditionNotMetError)
from paddle_tpu.inference import DuplicateRequestError, GenerationPool
from paddle_tpu.jit import DecodeSession
from paddle_tpu.jit.decode import (FINISH_EOS, FINISH_LENGTH,
                                   classify_finish)
from paddle_tpu.models import TransformerLM
from paddle_tpu.serving import (MetricsRegistry, QueueFullError,
                                RequestState, ServingEngine)


def _tiny_model(vocab=128, hidden=32, heads=2, layers=1,
                max_position=256):
    # smaller than the decode-test models on purpose: these tests pin
    # SCHEDULER behavior (lifecycle, allocator reclaim, metrics), and
    # every engine pays a fresh prefill+decode compile — the model just
    # needs a real cache-threaded forward, not representative math
    pt.seed(0)
    return TransformerLM(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=2 * hidden,
        max_position=max_position, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


class FakeClock:
    """Deterministic monotonic time for deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- token identity + compile counts (the acceptance contract) ----------

@pytest.mark.parametrize("layout_kw", [
    pytest.param({}, id="dense"),
    pytest.param(dict(cache_layout="paged", block_size=8), id="paged"),
])
def test_streamed_greedy_token_identical_to_pool_run(model, layout_kw):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 11, 7, 3)]
    ref = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                         **layout_kw)
    rids = [ref.submit(p, 6) for p in prompts]
    want = ref.run()

    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16],
                        **layout_kw)
    streams = [eng.submit(p, 6) for p in prompts]
    # iterating a stream pumps the engine inline — tokens arrive as the
    # pool emits them, single-threaded
    for s, rid in zip(streams, rids):
        np.testing.assert_array_equal(np.asarray(list(s), np.int32),
                                      want[rid])
        st = s.result(timeout_s=0)
        assert st.state == RequestState.DONE
        assert st.finish_reason == FINISH_LENGTH
        assert st.new_tokens == 6 and st.prompt_tokens == len(
            prompts[rids.index(rid)])
        np.testing.assert_array_equal(st.tokens, want[rid])
        assert st.ttft_s is not None and st.total_s >= st.ttft_s >= 0
    # exactly-two-compiles survives the serving layer: one prefill
    # bucket + one batched pool decode (+ the slot-insert splice)
    counts = eng.compile_counts()
    assert counts["prefill"] == 1
    assert counts["pool_decode"] == 1 and counts["slot_insert"] == 1


# -- deadlines ----------------------------------------------------------

def test_deadline_expiry_frees_slot_and_blocks(model):
    clock = FakeClock()
    # ONE slot so the engine exercises BOTH expiry paths in one run: a
    # decoding request whose deadline passes mid-generation, and a
    # queued request whose deadline passes before it ever gets a slot.
    # Slot selection is deadline-aware (§5j: earliest deadline wins the
    # free slot within a priority class), so `b` — submitted SECOND but
    # with the tighter deadline — takes the slot and `a` waits
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                        cache_layout="paged", block_size=8, clock=clock)
    baseline = eng.cache_stats()
    a = eng.submit(np.zeros(5, np.int32), 40, deadline_s=1.0)
    b = eng.submit(np.zeros(7, np.int32), 20, deadline_s=0.5)
    eng.pump(3)  # `b` admitted (earliest deadline) + decode; `a` waits
    assert eng.request_state(b.request_id) == RequestState.DECODING
    assert eng.request_state(a.request_id) == RequestState.QUEUED
    assert eng.cache_stats()["mapped_blocks"] > 0
    clock.advance(0.6)  # past b's deadline, mid-decode
    eng.pump(2)  # expiry sweep fires, then `a` takes the freed slot
    stb = b.result(timeout_s=0)
    assert stb.state == RequestState.EXPIRED
    assert stb.finish_reason == "deadline"
    assert 0 < stb.new_tokens < 20  # partial output rides in the status
    assert eng.request_state(a.request_id) == RequestState.DECODING
    clock.advance(0.5)  # past a's deadline too
    assert eng.pump(1) is False  # expiry sweep fires before the step
    st = a.result(timeout_s=0)
    assert st.state == RequestState.EXPIRED
    assert st.finish_reason == "deadline"
    assert 0 < st.new_tokens < 40
    # the slot and every paged block came back: no leak
    stats = eng.cache_stats()
    assert stats["mapped_blocks"] == 0
    assert stats["free_blocks"] == baseline["free_blocks"]
    snap = eng.metrics.snapshot()
    assert snap["serving_requests_expired_total"] == 2
    assert snap["serving_ttft_seconds"]["count"] == 2


def test_submit_rejects_nonpositive_deadline(model):
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8])
    with pytest.raises(InvalidArgumentError, match="deadline_s"):
        eng.submit(np.zeros(4, np.int32), 2, deadline_s=0.0)


# -- admission control --------------------------------------------------

def test_queue_full_fails_fast_and_counts(model):
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                        max_queue=2)
    streams = [eng.submit(np.zeros(4, np.int32), 4) for _ in range(2)]
    with pytest.raises(QueueFullError, match="max_queue"):
        eng.submit(np.zeros(4, np.int32), 4)
    assert eng.metrics.snapshot()[
        "serving_admission_rejected_total"] == 1
    # the accepted requests are unharmed by the rejection
    while eng.pump(16):
        pass
    assert all(s.result(timeout_s=0).state == RequestState.DONE
               for s in streams)
    # queue drained: admission opens again
    eng.submit(np.zeros(4, np.int32), 2)


def test_duplicate_request_id_typed_error_names_id(model):
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8])
    eng.submit(np.zeros(4, np.int32), 2, request_id="job-17")
    with pytest.raises(DuplicateRequestError, match="job-17"):
        eng.submit(np.zeros(4, np.int32), 2, request_id="job-17")
    # still an InvalidArgumentError for pre-existing broad handlers
    assert issubclass(DuplicateRequestError, InvalidArgumentError)
    # the failed submit left no engine record behind
    assert eng.live_requests == 1


# -- cancellation -------------------------------------------------------

def test_cancel_mid_decode_frees_blocks_without_corrupting_survivor(
        model):
    rng = np.random.RandomState(3)
    pa = rng.randint(0, 128, (5,)).astype("int32")
    pb = rng.randint(0, 128, (9,)).astype("int32")
    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16],
                        cache_layout="paged", block_size=8)
    free0 = eng.cache_stats()["free_blocks"]
    a = eng.submit(pa, 30)
    b = eng.submit(pb, 6)
    eng.pump(2)
    assert eng.cancel(a.request_id) is True
    assert eng.cancel(a.request_id) is False  # idempotent once terminal
    st = a.result(timeout_s=0)
    assert st.state == RequestState.CANCELLED
    assert st.finish_reason == "cancelled" and 0 < st.new_tokens < 30
    while eng.pump(8):
        pass
    # the survivor's tokens are exactly the standalone generation: the
    # cancelled slot's blocks were reusable without cross-request leaks
    sess = DecodeSession(model, max_len=64, buckets=[16])
    np.testing.assert_array_equal(b.result(timeout_s=0).tokens,
                                  sess.generate(pb[None], 6)[0])
    assert eng.cache_stats()["free_blocks"] == free0
    snap = eng.metrics.snapshot()
    assert snap["serving_requests_cancelled_total"] == 1
    assert snap["serving_requests_completed_total"] == 1
    # shutdown(drain=False) on the same engine: in-flight work is
    # CANCELLED, not finished
    c = eng.submit(np.zeros(4, np.int32), 30)
    eng.pump(1)
    eng.shutdown(drain=False)
    assert c.result(timeout_s=0).state == RequestState.CANCELLED
    assert eng.cache_stats()["free_blocks"] == free0


def test_pool_release_and_cancel_surface(model):
    # the inference-layer half: release(slot) and cancel(rid) free real
    # allocator state and run() never returns aborted requests
    pool = GenerationPool(model, max_len=64, slots=2, buckets=[16],
                         cache_layout="paged", block_size=8)
    free0 = len(pool._free_blocks)
    ra = pool.submit(np.zeros(5, np.int32), 20)
    rb = pool.submit(np.zeros(6, np.int32), 4)
    pool.step()
    assert pool.active_count == 2
    assert pool.cancel(ra) == "active"
    assert pool.active_count == 1
    rc = pool.submit(np.zeros(4, np.int32), 3)
    assert pool.cancel(rc) == "queued"
    with pytest.raises(NotFoundError):
        pool.cancel("nope")
    results = pool.run()
    assert set(results) == {rb}
    assert len(pool._free_blocks) == free0
    # collect() on an already-run pool has nothing left
    with pytest.raises(NotFoundError):
        pool.collect(rb)


# -- drain / shutdown / weight swap -------------------------------------

def test_drain_stops_admissions_and_finishes_inflight(model):
    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16])
    s = eng.submit(np.zeros(5, np.int32), 4)
    assert eng.drain() is True
    assert s.result(timeout_s=0).state == RequestState.DONE
    assert eng.draining
    with pytest.raises(PreconditionNotMetError, match="drain"):
        eng.submit(np.zeros(4, np.int32), 2)
    # hot weight swap rides the same engine: the pool's cached weight
    # values are dropped so the next step re-reads the model
    assert eng._pool._state_cache is not None
    eng.refresh_weights()
    assert eng._pool._state_cache is None




# -- finish reasons -----------------------------------------------------

def test_eos_finish_reason_threads_through(model):
    rng = np.random.RandomState(5)
    p = rng.randint(0, 128, (6,)).astype("int32")
    ref = DecodeSession(model, max_len=64, buckets=[16])
    toks = ref.generate(p[None], 6)[0]
    eos = int(toks[2])  # an id the model actually emits mid-stream
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                        eos_id=eos)
    st = eng.submit(p, 6).result()
    assert st.state == RequestState.DONE
    assert st.finish_reason == FINISH_EOS
    assert int(st.tokens[-1]) == eos and st.new_tokens <= 3


def test_classify_finish_vocabulary():
    assert classify_finish([4, 7, 2], eos_id=2) == FINISH_EOS
    assert classify_finish([4, 7, 2], eos_id=9) == FINISH_LENGTH
    assert classify_finish([4, 7, 2], eos_id=None) == FINISH_LENGTH
    assert classify_finish([], eos_id=2) == FINISH_LENGTH


# -- metrics ------------------------------------------------------------

def test_metrics_snapshot_and_prometheus_render(model):
    reg = MetricsRegistry()
    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16],
                        metrics=reg)
    streams = [eng.submit(np.zeros(n, np.int32), 4) for n in (4, 6)]
    while eng.pump(8):
        pass
    assert all(s.result(timeout_s=0).state == RequestState.DONE
               for s in streams)
    snap = eng.metrics.snapshot()
    assert snap["serving_requests_submitted_total"] == 2
    assert snap["serving_requests_completed_total"] == 2
    assert snap["serving_tokens_emitted_total"] == 8
    assert snap["serving_ttft_seconds"]["count"] == 2
    # inter-token gaps: 3 per request (4 tokens each)
    assert snap["serving_inter_token_seconds"]["count"] == 6
    assert snap["serving_queue_depth"] == 0
    assert snap["serving_queue_depth_per_step"]["count"] >= 1
    assert snap["serving_tokens_per_sec"] > 0
    text = eng.metrics.render_prometheus()
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert 'serving_ttft_seconds_bucket{le="+Inf"} 2' in text
    assert "serving_ttft_seconds_count 2" in text
    assert "# TYPE serving_requests_completed_total counter" in text
    assert "serving_requests_completed_total 2" in text
    assert "# TYPE serving_queue_depth gauge" in text
    # a second engine over the SAME registry accumulates (fleet-level
    # counters survive engine restarts) instead of clobbering
    eng2 = ServingEngine(model, max_len=32, slots=1, buckets=[8],
                         metrics=reg)
    eng2.submit(np.zeros(4, np.int32), 2)
    while eng2.pump(4):
        pass
    assert reg.snapshot()["serving_requests_completed_total"] == 3


def test_kv_resident_bytes_gauge_dtype_aware(model):
    # the resident-bytes gauge reports the WHOLE pool allocation and is
    # dtype-aware: an int8 engine's resident bytes must show the
    # quantization win (<= 0.55x fp32: int8 K/V + riding fp32 scales)
    engines = {}
    for dtype in ("float32", "int8"):
        eng = ServingEngine(model, max_len=64, slots=2, buckets=[16],
                            cache_dtype=dtype)
        eng.submit(np.zeros(5, np.int32), 3)
        while eng.pump(8):
            pass
        snap = eng.metrics.snapshot()
        assert snap["serving_kv_resident_bytes"] == \
            eng.cache_stats()["pool_bytes"]
        engines[dtype] = snap["serving_kv_resident_bytes"]
        assert "serving_kv_resident_bytes" in eng.metrics \
            .render_prometheus()
    assert 0 < engines["int8"] <= 0.55 * engines["float32"]
    # paged int8: resident = the block-pool allocation, not slots*max_len
    paged = ServingEngine(model, max_len=64, slots=2, buckets=[16],
                          cache_layout="paged", block_size=8,
                          num_blocks=5, cache_dtype="int8")
    paged.submit(np.zeros(5, np.int32), 3)
    while paged.pump(8):
        pass
    snap = paged.metrics.snapshot()
    assert snap["serving_kv_resident_bytes"] == \
        paged.cache_stats()["pool_bytes"]
    assert snap["serving_kv_resident_bytes"] < engines["int8"]


def test_metrics_registry_typing_and_quantile():
    from paddle_tpu.serving import Histogram
    reg = MetricsRegistry()
    c = reg.counter("x_total", "help")
    assert reg.counter("x_total") is c  # create-or-get
    with pytest.raises(InvalidArgumentError, match="x_total"):
        reg.gauge("x_total")
    hh = reg.histogram("h_hist", buckets=(0.1, 1.0))
    assert reg.histogram("h_hist", buckets=(0.1, 1.0)) is hh
    with pytest.raises(InvalidArgumentError, match="buckets"):
        reg.histogram("h_hist", buckets=(0.1, 2.0))  # silent mis-bucket
    with pytest.raises(InvalidArgumentError):
        reg.counter("bad name")
    with pytest.raises(InvalidArgumentError):
        c.inc(-1)
    h = Histogram("h", buckets=(0.1, 1.0, 10.0))
    assert h.quantile(0.5) is None
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.quantile(0.0) == 0.1
    assert h.quantile(0.5) == 1.0
    assert h.quantile(1.0) == 10.0
    h.observe(100.0)
    assert h.quantile(1.0) == float("inf")
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["buckets"]["+Inf"] == 5
    # reset() zeros counts but keeps the bucket layout (it drops a
    # warm-up's compile gaps from the serving ITL quantiles)
    h.reset()
    assert h.quantile(0.5) is None and h.count == 0 and h.sum == 0.0
    h.observe(0.5)
    assert h.quantile(1.0) == 1.0  # same buckets after reset


# -- the two drive modes share one code path ----------------------------

def test_pump_refused_while_thread_owns_engine(model):
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8])
    eng.start()
    try:
        with pytest.raises(PreconditionNotMetError, match="pump"):
            eng.pump(1)
    finally:
        eng.shutdown()


def test_callers_turn_waits_for_those_before_the_lock():
    # the count behind the loop's turn for submit and cancel, alone:
    # nobody waiting costs nothing, a caller that was let in ends the
    # wait, and one that never gets the lock holds the loop turn_s only
    from paddle_tpu.serving.engine import _Callers
    callers = _Callers()
    t0 = time.perf_counter()
    callers.let_in(5.0)
    assert time.perf_counter() - t0 < 1.0
    callers.arrive()
    threading.Timer(0.05, callers.admitted).start()
    t0 = time.perf_counter()
    callers.let_in(30.0)
    assert 0.04 <= time.perf_counter() - t0 < 10.0
    callers.arrive()
    t0 = time.perf_counter()
    callers.let_in(0.05)
    assert 0.04 <= time.perf_counter() - t0 < 10.0


def test_submit_gets_the_lock_between_two_ticks(model):
    # the step loop gives the lock up and takes it again at once: a
    # submit used to stand before it for tens of ticks while the engine
    # was busy (benchmark, PR 32: the closed-loop cells' queues filled
    # by luck).  Counted in TICKS, so a loaded host does not change it
    eng = ServingEngine(model, max_len=256, slots=2, buckets=[8])
    ticks = [0]
    real_step = eng._pool.step

    def step(*a, **k):
        ticks[0] += 1
        return real_step(*a, **k)

    eng._pool.step = step
    eng.start()
    try:
        prompt = np.arange(5, dtype="int32")
        busy = eng.submit(prompt, 240)
        while ticks[0] < 3:
            time.sleep(0.001)
        waited = []
        for _ in range(9):
            before = ticks[0]
            eng.submit(prompt, 1)
            waited.append(ticks[0] - before)
        assert sorted(waited)[len(waited) // 2] <= 2, waited
        assert busy.result(timeout_s=120.0).state == RequestState.DONE
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_background_thread_mode_token_identical(model):
    # the one threaded test (slow-marked: the tier-1 CPU budget forbids
    # concurrent load): the owned step loop must produce exactly the
    # pump()-mode tokens, because both run the same _tick
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 128, (n,)).astype("int32")
               for n in (5, 11, 7)]
    ref = GenerationPool(model, max_len=64, slots=2, buckets=[16])
    want = [ref.generate([p], 6)[0] for p in prompts]
    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16]).start()
    try:
        streams = [eng.submit(p, 6) for p in prompts]
        statuses = [s.result(timeout_s=120.0) for s in streams]
        for st, w in zip(statuses, want):
            assert st is not None and st.state == RequestState.DONE
            np.testing.assert_array_equal(st.tokens, w)
    finally:
        eng.shutdown()
