"""The stdlib HTTP front end over the serving engine.

The tier-1 tests stay SINGLE-THREADED: the request handler is driven
against an in-memory fake socket, so the handler thread IS the test
thread and the engine runs in deterministic pump mode (stream iteration
pumps it inline) — full request→stream→response coverage with no
concurrency in the time budget.  One slow-marked test runs the real
``ThreadingHTTPServer`` + ``urllib`` round trip.
"""
import io
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.models import TransformerLM
from paddle_tpu.serving import (ServingEngine, ServingHTTPFrontend,
                                parse_generate_request)
from paddle_tpu.serving.http import _make_handler


def _tiny_model():
    pt.seed(0)
    return TransformerLM(vocab_size=128, hidden_size=32, num_layers=1,
                         num_heads=2, intermediate_size=64,
                         max_position=256, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


# -- request parsing (pure, no engine) -----------------------------------

def test_parse_generate_request_valid():
    ids, max_new, rid, deadline, prio, tenant = parse_generate_request(
        json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4,
                    "request_id": "job-1", "deadline_s": 2.5,
                    "priority": "high", "tenant": "acme"}).encode())
    np.testing.assert_array_equal(ids, [1, 2, 3])
    assert ids.dtype == np.int32
    assert max_new == 4 and rid == "job-1" and deadline == 2.5
    assert prio == 1 and tenant == "acme"  # named class normalized
    ids, max_new, rid, deadline, prio, tenant = parse_generate_request(
        b'{"prompt": [7], "max_new_tokens": 1}')
    assert rid is None and deadline is None
    assert prio == 0 and tenant is None
    # raw integer priorities pass through unmapped
    assert parse_generate_request(
        b'{"prompt": [7], "max_new_tokens": 1, "priority": -3}')[4] == -3


_OK = b'{"prompt": [1], "max_new_tokens": 2, '
MALFORMED = {
    "not_json": (b"not json", "JSON"),
    "not_an_object": (b'[1, 2]', "object"),
    "no_prompt": (b'{"max_new_tokens": 3}', "prompt"),
    "empty_prompt": (b'{"prompt": [], "max_new_tokens": 3}', "prompt"),
    "prompt_a_string": (b'{"prompt": "abc", "max_new_tokens": 3}',
                        "prompt"),
    "prompt_holds_a_bool": (b'{"prompt": [1, true], "max_new_tokens": 3}',
                            "prompt"),
    "no_max_new_tokens": (b'{"prompt": [1]}', "max_new_tokens"),
    "max_new_tokens_zero": (b'{"prompt": [1], "max_new_tokens": 0}',
                            "max_new_tokens"),
    "max_new_tokens_a_float": (b'{"prompt": [1], "max_new_tokens": 2.5}',
                               "max_new_tokens"),
    "deadline_a_string": (_OK + b'"deadline_s": "soon"}', "deadline_s"),
    "deadline_a_bool": (_OK + b'"deadline_s": true}', "deadline_s"),
    "token_past_int32": (b'{"prompt": [34359738368], "max_new_tokens": 2}',
                         "int32"),
    "request_id_an_object": (_OK + b'"request_id": {"a": 1}}',
                             "request_id"),
    "request_id_a_list": (_OK + b'"request_id": [1]}', "request_id"),
    "priority_unknown": (_OK + b'"priority": "urgent"}', "priority"),
    "priority_a_bool": (_OK + b'"priority": true}', "priority"),
    "priority_a_float": (_OK + b'"priority": 1.5}', "priority"),
    "tenant_a_number": (_OK + b'"tenant": 7}', "tenant"),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_parse_generate_request_malformed(case):
    body, why = MALFORMED[case]
    with pytest.raises(InvalidArgumentError, match=why):
        parse_generate_request(body)


# -- the handler against an in-memory socket (single-threaded) -----------

class _FakeSocket:
    """Just enough socket for BaseHTTPRequestHandler: the request bytes
    come from a BytesIO, the response accumulates in ``out``."""

    def __init__(self, data: bytes):
        self._in = io.BytesIO(data)
        self.out = io.BytesIO()

    def makefile(self, mode, *args, **kwargs):
        return self._in

    def settimeout(self, value):  # handler sets its socket timeout
        pass

    def sendall(self, data):
        self.out.write(data)

    def close(self):
        pass


def _http(engine, method, path, body=b"", socket=None):
    """Run ONE request through the front end's handler class in-process;
    returns (status_code, header dict, body bytes)."""
    req = ("%s %s HTTP/1.1\r\nHost: t\r\nContent-Length: %d\r\n\r\n"
           % (method, path, len(body))).encode() + body
    sock = (socket or _FakeSocket)(req)
    _make_handler(engine)(sock, ("127.0.0.1", 0), None)
    raw = sock.out.getvalue()
    head, _, payload = raw.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").splitlines()
    code = int(lines[0].split()[1])
    headers = dict(l.split(": ", 1) for l in lines[1:] if ": " in l)
    return code, headers, payload


def test_post_generate_streams_tokens_and_status(model):
    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16])
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 128, (6,)).tolist()
    code, headers, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": prompt, "max_new_tokens": 5}).encode())
    assert code == 200
    assert headers["Content-Type"] == "application/x-ndjson"
    lines = [json.loads(l) for l in payload.splitlines()]
    toks = [l["token"] for l in lines if "token" in l]
    final = lines[-1]
    assert final["done"] and final["state"] == "DONE"
    assert final["finish_reason"] == "length"
    assert final["tokens"] == toks and len(toks) == 5
    assert final["prompt_tokens"] == 6 and final["new_tokens"] == 5
    # the request's own timeline rides the terminal line with no tracer
    # installed: the wait for the engine lock, which ttft_s and total_s
    # leave out, and the wait for a slot, which they include
    assert final["lock_wait_s"] >= 0
    assert 0 <= final["queue_wait_s"] <= final["ttft_s"] <= final["total_s"]
    # token-identical to the engine-free baseline
    from paddle_tpu.jit import DecodeSession
    want = DecodeSession(model, max_len=64, buckets=[16]).generate(
        np.asarray(prompt, np.int32)[None], 5)[0]
    np.testing.assert_array_equal(np.asarray(toks, np.int32), want)


def test_error_mapping(model):
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                        max_queue=4)
    # malformed body -> 400 with the actionable message
    code, _, payload = _http(eng, "POST", "/generate",
                             b'{"prompt": "nope"}')
    assert code == 400 and b"prompt" in payload
    # out-of-vocab prompt ids -> 400 naming the valid range (the
    # embedding gather would otherwise CLAMP them into garbage output)
    code, _, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": [999999], "max_new_tokens": 2}).encode())
    assert code == 400 and b"vocab" in payload
    # duplicate of a LIVE request id -> 409 naming the id (a finished
    # id becomes reusable, so the first "dup" is parked via the engine
    # API instead of a drained HTTP stream)
    eng.submit(np.zeros(4, np.int32), 4, request_id="dup")
    code, _, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": [1, 2], "max_new_tokens": 2,
                    "request_id": "dup"}).encode())
    assert code == 409 and b"dup" in payload
    while eng.pump(16):
        pass
    # queue full -> retryable 503 with Retry-After
    stuffed = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                            max_queue=1)
    stuffed.submit(np.zeros(4, np.int32), 20)
    stuffed.pump(1)  # admit it to the one slot (still decoding)
    stuffed.submit(np.zeros(4, np.int32), 4)  # fills the queue
    code, headers, payload = _http(
        stuffed, "POST", "/generate",
        json.dumps({"prompt": [1], "max_new_tokens": 2}).encode())
    assert code == 503 and headers.get("Retry-After") == "1"
    assert json.loads(payload)["retryable"] is True
    # draining -> 503 without the retry hint
    while stuffed.pump(16):
        pass
    stuffed.drain()
    code, headers, payload = _http(
        stuffed, "POST", "/generate",
        json.dumps({"prompt": [1], "max_new_tokens": 2}).encode())
    assert code == 503 and "Retry-After" not in headers
    assert json.loads(payload)["retryable"] is False
    # unknown paths -> 404 naming the two served endpoints
    assert _http(eng, "GET", "/nope")[0] == 404
    code, _, payload = _http(eng, "POST", "/nope", b"{}")
    assert code == 404 and b"/generate" in payload
    # a hand-crafted non-numeric Content-Length -> 400, never a dropped
    # connection with no response body
    for bad_len in (b"abc", b"-5"):
        sock = _FakeSocket(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                           b"Content-Length: " + bad_len + b"\r\n\r\n")
        _make_handler(eng)(sock, ("127.0.0.1", 0), None)
        raw = sock.out.getvalue()
        assert b" 400 " in raw.splitlines()[0]
        assert b"Content-Length" in raw
    # an oversized Content-Length -> 413 BEFORE any body bytes are
    # buffered (the cap is what stops one request OOMing the server)
    sock = _FakeSocket(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                       b"Content-Length: 8000000000\r\n\r\n")
    _make_handler(eng)(sock, ("127.0.0.1", 0), None)
    raw = sock.out.getvalue()
    assert b" 413 " in raw.splitlines()[0]
    assert b"limit" in raw


def test_get_metrics_renders_prometheus(model):
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16])
    s = eng.submit(np.zeros(4, np.int32), 3)
    while eng.pump(8):
        pass
    assert s.result(timeout_s=0).state == "DONE"
    code, headers, payload = _http(eng, "GET", "/metrics")
    assert code == 200
    assert headers["Content-Type"].startswith("text/plain")
    text = payload.decode()
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert "# TYPE serving_submit_lock_wait_seconds histogram" in text
    assert "serving_submit_lock_wait_seconds_count 1" in text
    assert "serving_requests_completed_total 1" in text
    assert text == eng.metrics.render_prometheus()


# -- observability surface: /healthz body, /debug endpoints --------------

def test_healthz_body_is_the_full_snapshot(model):
    # the body is the FULL health() snapshot (the watchdog records it;
    # the endpoint must not drop it): state, the last loop error
    # what/when/kind, restart + stall counters, the flight-dump slot
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8])
    code, _, payload = _http(eng, "GET", "/healthz")
    body = json.loads(payload)
    assert code == 200
    for field in ("state", "healthy", "live_requests", "queue_depth",
                  "loop_alive", "draining", "ticks_total",
                  "last_error", "last_error_at", "last_error_kind",
                  "restarts", "recoveries", "requests_recovered",
                  "ticks_stalled", "flight_dump", "started_at",
                  "uptime_s"):
        assert field in body, field
    # and after a recorded error the what/when/kind ride the body
    eng._health.note_error(1.25, RuntimeError("boom"), "loop")
    body = json.loads(_http(eng, "GET", "/healthz")[2])
    assert "boom" in body["last_error"]
    assert body["last_error_at"] == 1.25
    assert body["last_error_kind"] == "loop"


def test_healthz_started_at_and_uptime_track_the_engine_clock(model):
    # uptime is derived on the ENGINE's monotonic clock, so an
    # injected clock pins it exactly: birth at 100, probed at 103.5
    fake = {"now": 100.0}
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8],
                        clock=lambda: fake["now"])
    body = json.loads(_http(eng, "GET", "/healthz")[2])
    assert body["started_at"] == 100.0
    assert body["uptime_s"] == 0.0
    fake["now"] = 103.5
    body = json.loads(_http(eng, "GET", "/healthz")[2])
    assert body["started_at"] == 100.0
    assert body["uptime_s"] == 3.5


def test_slo_endpoint(model):
    from paddle_tpu.serving import Objective, SLOTracker

    # no tracker configured: 404 with an actionable hint, same
    # convention as the never-traced /debug endpoints
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8])
    code, _, payload = _http(eng, "GET", "/slo")
    assert code == 404 and b"SLOTracker" in payload
    # with objectives declared, the body is the tracker's snapshot
    tracker = SLOTracker(
        [Objective("availability", "availability", 0.99),
         Objective("ttft_p95", "ttft", 0.95, threshold_s=10.0)],
        fast_window=2, slow_window=8)
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                        slo=tracker)
    code, _, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": [3, 1, 4],
                    "max_new_tokens": 3}).encode())
    assert code == 200
    code, headers, payload = _http(eng, "GET", "/slo")
    assert code == 200
    assert headers["Content-Type"] == "application/json"
    body = json.loads(payload)
    assert body["fast_window_ticks"] == 2
    assert body["alerts_active"] == 0
    names = {o["name"]: o for o in body["objectives"]}
    assert set(names) == {"availability", "ttft_p95"}
    assert names["ttft_p95"]["threshold_s"] == 10.0
    assert names["availability"]["total_good"] == 1  # the DONE request
    # the SLO state also rides /healthz (the post-mortem contract)
    health = json.loads(_http(eng, "GET", "/healthz")[2])
    assert health["slo"] == {"alerts_active": 0, "alerting": [],
                             "ticks": tracker.ticks}


def test_healthz_stays_200_while_degraded_and_carries_the_level(model):
    # degradation is the system WORKING, not wedging: a degraded-but-
    # serving engine answers 200, with the ladder level and the parked-
    # victim count in the snapshot; 503 stays reserved for wedged/
    # loop-dead/stopped (§5j satellite contract)
    from paddle_tpu.serving import Objective, SLOTracker

    eng = ServingEngine(
        model, max_len=64, slots=1, buckets=[16],
        slo=SLOTracker([Objective("ttft_p95", "ttft", 0.95,
                                  threshold_s=0.5)],
                       fast_window=2, slow_window=4),
        degrade=True)
    body = json.loads(_http(eng, "GET", "/healthz")[2])
    assert body["degraded"] == 0 and body["preempted_requests"] == 0
    # force the ladder to its deepest rung (the closed-loop path is
    # pinned in tests/test_scheduling.py; this test pins the SURFACE)
    eng._set_degrade_level(3, ["ttft_p95"])
    stream = eng.submit(np.zeros(4, np.int32), 2, priority="high")
    code, _, payload = _http(eng, "GET", "/healthz")
    body = json.loads(payload)
    assert code == 200 and body["healthy"] is True
    assert body["state"] == "serving"
    assert body["degraded"] == 3
    # the /slo body carries what the alert is MAKING the engine do
    slo_body = json.loads(_http(eng, "GET", "/slo")[2])
    assert slo_body["degradation"]["level"] == 3
    assert slo_body["degradation"]["enabled"] is True
    # tighten-admission rung at the HTTP boundary: a below-floor
    # submit is shed 503 + Retry-After, retryable, while the floor
    # and above admit normally
    code, headers, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": [1, 2], "max_new_tokens": 2,
                    "priority": "low"}).encode())
    assert code == 503
    assert "Retry-After" in headers
    assert json.loads(payload)["retryable"] is True
    assert b"tightened" in payload or b"ladder" in payload
    assert eng.metrics.snapshot()[
        "serving_admission_tightened_total"] == 1
    while eng.pump(8):
        pass
    assert stream.result(timeout_s=0).state == "DONE"


def test_healthz_restoring_503_retry_after_then_200(model):
    """The §5m RESTORING pin: while a journal replay owns the engine,
    /healthz answers 503 WITH Retry-After (transient by construction —
    a rollout controller waits instead of killing the engine), submits
    are deferred with a live stream, and the flip back to 200 happens
    the moment replay ends."""
    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16])
    eng._begin_restore(retry_after_s=2.5)
    code, headers, payload = _http(eng, "GET", "/healthz")
    body = json.loads(payload)
    assert code == 503
    assert body["state"] == "restoring" and body["healthy"] is False
    assert body["restoring"] is True and body["retry_after_s"] == 2.5
    assert headers.get("Retry-After") == "3"  # ceil of the hint
    # admission during the window is DEFERRED, not dropped: a live
    # stream comes back, nothing reaches the pool yet
    stream = eng.submit(np.zeros(4, np.int32), 3)
    assert eng.live_requests == 0 and eng.queue_depth == 0
    eng._end_restore()
    code, headers, payload = _http(eng, "GET", "/healthz")
    assert code == 200 and "Retry-After" not in headers
    assert json.loads(payload)["restoring"] is False
    assert eng.live_requests == 1
    while eng.pump(8):
        pass
    assert stream.result(timeout_s=0).state == "DONE"


def test_debug_trace_and_flightrec_endpoints(model):
    from paddle_tpu.serving import trace

    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16])
    # tracing never enabled: both endpoints 404 with an actionable hint
    code, _, payload = _http(eng, "GET", "/debug/flightrec")
    assert code == 404 and b"start_trace" in payload
    code, _, payload = _http(eng, "GET", "/debug/trace?rid=x")
    assert code == 404
    eng.start_trace(capacity=512)
    try:
        code, _, payload = _http(
            eng, "POST", "/generate",
            json.dumps({"prompt": [3, 1, 4], "max_new_tokens": 3,
                        "request_id": "job-1"}).encode())
        assert code == 200
        # per-request timeline: queued -> ... -> done, JSON round-trip
        code, _, payload = _http(eng, "GET", "/debug/trace?rid=job-1")
        assert code == 200
        tl = json.loads(payload)
        names = [e["name"] for e in tl["events"]]
        # the caller's own id ties the wait for the engine lock to
        # the request: its timeline begins before it is queued
        assert names[:2] == ["submit.lock_wait", "req.queued"]
        # and ends with what the front did with its tokens, written
        # once the terminal line was
        assert names[-2:] == ["req.done", "http.stream"]
        # missing rid -> 400; unknown rid -> 404
        code, _, payload = _http(eng, "GET", "/debug/trace")
        assert code == 400 and b"rid" in payload
        assert _http(eng, "GET", "/debug/trace?rid=ghost")[0] == 404
        # the lock wait is on the queued mark of the timeline too
        assert tl["events"][1]["meta"]["lock_wait_s"] >= 0
        # the whole recorder, with its bounds
        code, _, payload = _http(eng, "GET", "/debug/flightrec")
        assert code == 200
        rec = json.loads(payload)
        assert rec["capacity"] == 512 and "deep_timing" not in rec
        assert rec["dropped"] == 0 and rec["events"]
        spans = {e["name"] for e in rec["events"] if "dur_s" in e}
        assert {"submit.lock_wait", "tick", "tick.govern",
                "tick.observe", "tick.journal"} <= spans
    finally:
        eng.stop_trace()
    # the engine keeps the last tracer: export still served post-stop
    assert _http(eng, "GET", "/debug/flightrec")[0] == 200


# -- robustness surface: /healthz, shedding, disconnect seam -------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_healthz_flips_200_503_200_across_wedge_and_restart(model):
    # the SystemExit killing the loop below IS the dead-loop scenario;
    # pytest's threadexception plugin would otherwise warn about it
    from paddle_tpu.serving import Supervisor

    clock = _FakeClock()
    eng = ServingEngine(model, max_len=32, slots=1, buckets=[8],
                        clock=clock)
    sup = Supervisor(eng, stall_timeout_s=0.5, clock=clock)
    code, _, payload = _http(eng, "GET", "/healthz")
    body = json.loads(payload)
    assert code == 200 and body["healthy"] and body["state"] == "idle"
    # an injected wedge: a tick that started and never finished, past
    # the supervisor's stall timeout
    eng._health.note_tick_start(clock())
    clock.advance(1.0)
    assert sup.check_once() == ["stall-detected"]
    code, _, payload = _http(eng, "GET", "/healthz")
    body = json.loads(payload)
    assert code == 503
    assert body["state"] == "wedged" and body["ticks_stalled"] == 1
    # the wedge clears (tick completes): healthy again, episode closed
    eng._health.note_tick_end(clock())
    code, _, payload = _http(eng, "GET", "/healthz")
    assert code == 200 and json.loads(payload)["healthy"]
    # and across a WATCHDOG RESTART: kill the background loop, let the
    # supervisor restart it, health reports the restart and stays 200
    eng.start()
    try:
        t_old = eng._thread

        def boom():
            raise SystemExit

        eng._tick = boom
        t_old.join(timeout=10.0)
        assert not t_old.is_alive()
        del eng._tick
        assert _http(eng, "GET", "/healthz")[0] == 503  # loop-dead
        assert sup.check_once() == ["loop-restarted"]
        code, _, payload = _http(eng, "GET", "/healthz")
        body = json.loads(payload)
        assert code == 200 and body["restarts"] == 1
    finally:
        eng.shutdown()


def test_unattainable_deadline_maps_to_503_with_retry_after(model):
    eng = ServingEngine(model, max_len=128, slots=1, buckets=[8])
    # warm the tick-rate observation, then pile up a backlog
    eng.submit(np.zeros(4, np.int32), 3)
    while eng.pump(8):
        pass
    eng.submit(np.zeros(4, np.int32), 100)
    eng.pump(2)
    code, headers, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": [1, 2], "max_new_tokens": 20,
                    "deadline_s": 1e-9}).encode())
    assert code == 503
    assert int(headers["Retry-After"]) >= 1
    body = json.loads(payload)
    assert body["retryable"] is True and "shed" in body["error"]
    assert eng.metrics.snapshot()["serving_requests_shed_total"] == 1
    while eng.pump(200):
        pass


def test_http_write_fault_cancels_like_a_disconnect(model):
    from paddle_tpu.serving import faults
    from paddle_tpu.serving.faults import FaultPlane, FaultSpec

    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16],
                        cache_layout="paged", block_size=8)
    free0 = eng.cache_stats()["free_blocks"]
    plane = FaultPlane([FaultSpec(
        "http.write", error=ConnectionResetError("injected disconnect"),
        after=2, times=1)])
    with faults.injected(plane):
        code, _, payload = _http(
            eng, "POST", "/generate",
            json.dumps({"prompt": [3, 1, 4],
                        "max_new_tokens": 30}).encode())
    assert code == 200  # headers + two token lines went out first
    lines = [json.loads(l) for l in payload.splitlines()]
    assert len(lines) == 2 and all("token" in l for l in lines)
    # the disconnect cancelled the request: slot and blocks reclaimed,
    # no terminal line was ever written for a consumer that left
    assert eng.live_requests == 0
    assert eng.cache_stats()["free_blocks"] == free0
    assert eng.metrics.snapshot()[
        "serving_requests_cancelled_total"] == 1


# -- the front reports from inside: one ``http.stream`` a request --------

def _generate(eng, rid, n=5, **kw):
    code, _, payload = _http(
        eng, "POST", "/generate",
        json.dumps({"prompt": [3, 1, 4], "max_new_tokens": n,
                    "request_id": rid}).encode(), **kw)
    assert code == 200
    return payload.splitlines(keepends=True)


def _streams_of(eng, monkeypatch):
    """Every stream ``eng.submit`` hands out from here on."""
    streams, submit = [], eng.submit

    def keeping(*a, **kw):
        streams.append(submit(*a, **kw))
        return streams[-1]

    monkeypatch.setattr(eng, "submit", keeping)
    return streams


def _http_streams(tracer):
    return [e for e in tracer.recorder.snapshot()
            if e.name == "http.stream"]


def _check_lag(ev, rid, lines):
    assert ev.rid == rid and ev.dur_s is None
    assert set(ev.meta) == {"lines", "lag_sum_s", "lag_max_s", "cpu_s"}
    assert ev.meta["lines"] == lines
    assert 0.0 <= ev.meta["lag_max_s"] <= ev.meta["lag_sum_s"]
    assert ev.meta["lag_sum_s"] <= lines * ev.meta["lag_max_s"]
    assert ev.meta["cpu_s"] >= 0.0


def test_a_traced_stream_says_what_its_lines_waited(model, monkeypatch):
    from paddle_tpu.serving import trace

    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16])
    streams = _streams_of(eng, monkeypatch)
    # no tracer: no stamp, no deque for stamps, no event
    plain = _generate(eng, "plain")
    assert streams[-1]._stamps is None
    assert streams[-1]._take_stamp(0) is None
    tracer = eng.start_trace(capacity=512)
    try:
        traced = _generate(eng, "traced")
        # ONE instant a request, after its terminal line, on its timeline
        (ev,) = _http_streams(tracer)
        _check_lag(ev, "traced", 5)
        # every stamp was taken by the line it was for
        assert not streams[-1]._stamps
        code, _, payload = _http(eng, "GET", "/debug/trace?rid=traced")
        names = [e["name"] for e in json.loads(payload)["events"]]
        assert names[-2:] == ["req.done", "http.stream"]
        _generate(eng, "second", n=3)
        assert [e.rid for e in _http_streams(tracer)] == ["traced",
                                                          "second"]
        _check_lag(_http_streams(tracer)[-1], "second", 3)
    finally:
        eng.stop_trace()
    # the tokens byte for byte what they are with no tracer installed
    assert len(plain) == len(traced) == 6
    assert plain[:5] == traced[:5]
    # under injected clocks that advance by one a reading: whole numbers,
    # a reading or more between a put and its flush, and between the
    # handler's two readings of its CPU clock
    import itertools
    wall, cpu = itertools.count(), itertools.count()
    with trace.tracing(trace.Tracer(
            capacity=512, clock=lambda: float(next(wall)),
            cpu_clock=lambda: float(next(cpu)))) as tracer:
        _generate(eng, "counted", n=4)
    (ev,) = _http_streams(tracer)
    _check_lag(ev, "counted", 4)
    assert ev.meta["lag_max_s"] >= 1.0 and ev.meta["cpu_s"] >= 1.0
    assert all(v == int(v) for v in ev.meta.values())


def test_a_stream_counts_only_lines_put_under_the_tracer(model,
                                                         monkeypatch):
    from paddle_tpu.serving import trace

    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16])
    streams = _streams_of(eng, monkeypatch)
    tracer = trace.Tracer(capacity=512)

    class InstallsAfterTwoLines(_FakeSocket):
        sent = 0

        def sendall(self, data):
            super().sendall(data)
            self.sent += data.count(b'"token"')
            if self.sent == 2 and trace.active() is None:
                trace.install(tracer)

    try:
        lines = _generate(eng, "late", n=6, socket=InstallsAfterTwoLines)
    finally:
        trace.uninstall()
    assert len(lines) == 7
    # the two tokens put before the tracer came have no stamp and are
    # not counted; each later one is, in its own place in the stream
    (ev,) = _http_streams(tracer)
    _check_lag(ev, "late", 4)
    assert not streams[-1]._stamps


def test_a_disconnect_ends_the_stream_with_its_event(model):
    from paddle_tpu.serving import faults
    from paddle_tpu.serving.faults import FaultPlane, FaultSpec

    eng = ServingEngine(model, max_len=64, slots=1, buckets=[16])
    tracer = eng.start_trace(capacity=512)
    plane = FaultPlane([FaultSpec(
        "http.write", error=ConnectionResetError("injected disconnect"),
        after=2, times=1)])
    try:
        with faults.injected(plane):
            lines = _generate(eng, "gone", n=30)
    finally:
        eng.stop_trace()
    assert len(lines) == 2
    # the two lines that were flushed, and no more: the client was gone
    (ev,) = _http_streams(tracer)
    _check_lag(ev, "gone", 2)
    assert eng.live_requests == 0


# -- the real server (threaded: slow-marked per the tier-1 budget) -------

@pytest.mark.slow
def test_real_server_round_trip(model):
    import urllib.error
    import urllib.request

    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16]).start()
    front = ServingHTTPFrontend(eng).start()
    try:
        base = "http://%s:%d" % front.address
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [3, 1, 4],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        lines = []
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            for line in resp:
                lines.append(json.loads(line))
        assert lines[-1]["done"] and lines[-1]["new_tokens"] == 4
        assert [l["token"] for l in lines[:-1]] == lines[-1]["tokens"]
        with urllib.request.urlopen(base + "/metrics",
                                    timeout=30) as resp:
            assert "serving_tokens_emitted_total" in resp.read().decode()
        try:
            urllib.request.urlopen(
                urllib.request.Request(base + "/generate", data=b"bad"),
                timeout=30)
            raise AssertionError("malformed body must 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        front.shutdown()
        eng.shutdown()


def test_frontend_lifecycle_guards(model):
    from paddle_tpu.core.errors import PreconditionNotMetError

    eng = ServingEngine(model, max_len=64, slots=2, buckets=[16])
    # shutdown before any serve loop: must return (BaseServer.shutdown
    # would wait forever on an event only serve_forever sets), and be
    # idempotent
    f1 = ServingHTTPFrontend(eng)
    f1.shutdown()
    f1.shutdown()
    with pytest.raises(PreconditionNotMetError):
        f1.start()           # socket is closed: refuse, don't leak a
    with pytest.raises(PreconditionNotMetError):
        f1.serve_forever()   # dead serve thread on a dead fd
    # one serve loop per frontend: a started frontend refuses a second
    # blocking loop on the same socket
    f2 = ServingHTTPFrontend(eng).start()
    try:
        assert f2.start() is f2          # idempotent
        with pytest.raises(PreconditionNotMetError):
            f2.serve_forever()
    finally:
        f2.shutdown()
