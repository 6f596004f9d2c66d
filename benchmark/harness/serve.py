"""The serving cells: ``POST /generate`` on ``ServingHTTPFrontend`` ->
``ServingEngine`` -> ``GenerationPool.step``, driven by the traffic
generator from one thread, measured on the client's clock."""
from __future__ import annotations

import gc
import json
import sys
import threading
import time

from . import device, traffic as traffic_mod, weights, window
from .client import StreamClient


WARM_INDEX = 1 << 30     # request indices of the warm-up, never checked
TRACE_LEAD_S = 2.0       # the profiler is asked for this long before the
                         # traced stretch is due: it takes about a second


def say(msg: str) -> None:
    print(msg, flush=True)


def model_sizes(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("vocab_size", "hidden_size", "num_layers",
                                "num_heads", "intermediate_size",
                                "max_position", "causal")}


def load_weights(model, cfg: dict, seed: int, made: dict = None) -> None:
    """Put the benchmark's seeded weights into the program's model."""
    named = weights.to_program_names(made or weights.make_weights(
        model_sizes(cfg), seed))
    del made
    for name, p in model.named_parameters():
        p._replace_value(named.pop(name))
    if named:
        raise RuntimeError("the program's model lacks %s" % sorted(named))


def build(cfg: dict, seed: int):
    """The model and engine, as ``chip_smoke.py`` builds them, with the
    benchmark's weights."""
    import paddle_tpu as pt
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    # the benchmark's weights first: while the program initialises its own
    # the two sets are on the device together, and nothing else is yet
    made = weights.make_weights(model_sizes(cfg), seed)
    pt.seed(weights.seed32(seed))
    model = TransformerLM(**model_sizes(cfg), dropout=0.0)
    model.eval()
    load_weights(model, cfg, seed, made)
    del made
    eng = dict(cfg["engine"])
    engine = ServingEngine(model, max_len=cfg["max_len"], **eng)
    front = ServingHTTPFrontend(engine)
    return model, engine, front


def buckets_used(cfg: dict, schedule) -> list:
    """The prefill buckets this cell's prompt lengths fall into."""
    buckets = sorted(cfg["engine"]["buckets"])
    return sorted({min(b for b in buckets if b >= n)
                   for n in schedule.prompts})


def warm_up(client_factory, cfg: dict, schedule) -> None:
    """One request through every executable the window will use: each
    prefill bucket, the slot insert and the batched decode step."""
    client = client_factory()
    for n, b in enumerate(buckets_used(cfg, schedule)):
        rec = {"index": WARM_INDEX + n}
        client.send(rec, schedule.token_ids(WARM_INDEX + n, b,
                                            cfg["vocab_size"]), 3)
        deadline = time.perf_counter() + 1500
        while rec["done"] is None and time.perf_counter() < deadline:
            client.poll(1.0)
        if rec["status"] != "ok":
            raise RuntimeError("warm-up request for bucket %d: %s %s"
                               % (b, rec["status"], rec.get("error")))
    client.abandon()


class Profiler:
    """The profiler's trace, started from a side thread so that the
    generator keeps its schedule, with ``bench.sync`` marks that tie the
    trace's clock to ``perf_counter``.  ``before`` runs on that thread
    first (the serving cells switch the engine's tracer on there)."""

    def __init__(self, jax, trace_dir: str, before=None):
        self.jax, self.dir, self.before = jax, trace_dir, before
        self.started = threading.Event()
        self.syncs = []
        self._thread = None

    def _start(self) -> None:
        if self.before:
            self.before()
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started.set()

    def begin(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._start, daemon=True)
            self._thread.start()

    def mark(self) -> None:
        self.syncs.append(time.perf_counter())
        with self.jax.profiler.TraceAnnotation("bench.sync"):
            pass

    def stop(self) -> None:
        self._thread.join()
        self.jax.profiler.stop_trace()

    def stop_aside(self) -> None:
        """Stop from a side thread (writing the trace takes seconds) so
        that the generator keeps its schedule; ``join`` waits for it."""
        self._stopper = threading.Thread(target=self.stop, daemon=True)
        self._stopper.start()

    def join(self) -> None:
        self._stopper.join()


def drive(client, schedule, cfg: dict, tr: dict, seconds: float,
          profiler=None) -> dict:
    """Send the traffic and read the streams until the window closes.
    Returns ``{"records", "t_open", "t_close", "traced"}`` on
    ``perf_counter``.

    With a profiler the traffic runs as long, but the window that the
    client's stamps are read over closes when tracing begins, ``trace_s``
    and a lead before the end: the engine's tracer changes what a client
    waits (it halves the wait for a first token, my chip runs, PR 25), so
    the client is read while nothing traces, and ``traced`` is the stretch
    after it that the profiler and the engine's spans cover."""
    clock = time.perf_counter
    vocab = cfg["vocab_size"]
    records = []

    def send(req, due):
        rec = dict(req, due=due)
        records.append(rec)
        client.send(rec, schedule.token_ids(req["index"],
                                            req["prompt_tokens"], vocab),
                    req["output_tokens"])

    t_start = clock()
    t_open = t_start + tr["warmup_s"]
    t_end = t_open + seconds
    t_trace = t_end - tr["trace_s"] - TRACE_LEAD_S if profiler else None
    traced_from = None
    horizon = tr["warmup_s"] + seconds + 60.0
    nxt = 0
    if not schedule.open:
        for _ in range(tr["clients"]):
            send(schedule.request(nxt), clock())
            nxt += 1
            client.poll(0.002)      # let the front's accept loop keep up
    while True:
        now = clock()
        if now >= t_end:
            break
        if profiler and now >= t_trace:
            profiler.begin()
            if traced_from is None and profiler.started.is_set():
                profiler.mark()
                traced_from = clock()
        wake = min(now + 0.05, t_end)
        if schedule.open:
            while True:
                req = schedule.request(nxt)
                if req["due_s"] > horizon:
                    raise RuntimeError("traffic outran its horizon")
                due = t_start + req["due_s"]
                if due > now:
                    wake = min(wake, due)
                    break
                send(req, due)
                nxt += 1
        for rec in client.poll(wake - clock()):
            if not schedule.open:
                send(schedule.request(nxt), clock())
                nxt += 1
    traced = None
    if profiler:
        if traced_from is None:
            profiler.started.wait(30.0)
            raise Refused("the profiler had not started when the window "
                          "closed")
        profiler.mark()
        traced = (traced_from, clock())
        profiler.stop_aside()
    return {"records": records, "t_open": t_open,
            "t_close": t_trace if profiler else t_end, "traced": traced}


def drain(client, records, t_open, t_close, limit_s: float) -> None:
    """After the window: read on until every request due in it has its
    first token, so that no time to first token is cut short."""
    deadline = time.perf_counter() + limit_s
    def waiting():
        return any(t_open <= r["due"] < t_close and not r["stamps"]
                   and r["done"] is None for r in records)
    while waiting() and time.perf_counter() < deadline:
        client.poll(0.05)


def parse_counters(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#") and "{" not in line:
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


def engine_counters(engine) -> dict:
    c = parse_counters(engine.metrics.render_prometheus())
    spill = engine.spill_stats()
    return {"compiles": dict(engine.compile_counts()),
            "recoveries": c.get("serving_recoveries_total", 0.0),
            "requests_failed": c.get("serving_requests_failed_total", 0.0),
            "ticks_stalled": c.get("serving_ticks_stalled_total", 0.0),
            "preempts": spill.get("preempts_total", 0)}


def measure(run, jax) -> dict:
    """Set-up, warm-up and the window of one serving run.  ``run`` holds
    ``cfg``, ``traffic``, ``seed``, ``seconds``, ``trace`` and
    ``trace_dir``.  Returns what the window produced; the caller computes
    metrics, then calls ``out["free"]()`` before the reference runs."""
    cfg, tr = run["cfg"], run["traffic"]
    t0 = time.perf_counter()
    model, engine, front = build(cfg, run["seed"])
    t_built = time.perf_counter()
    engine.start()
    front.start()
    host, port = front.address
    schedule = traffic_mod.Schedule(tr, run["seed"])
    spans = None
    try:
        warm_up(lambda: StreamClient(host, port), cfg, schedule)
        t_warm = time.perf_counter()
        before = engine_counters(engine)
        profiler = tracer = None
        if run["trace"]:
            from paddle_tpu.serving import trace as engine_trace
            tracer = engine_trace.Tracer(capacity=1 << 20)
            profiler = Profiler(jax, run["trace_dir"],
                                before=lambda: engine_trace.install(tracer))
        seconds = max(run["seconds"], tr["trace_s"] + TRACE_LEAD_S + 1.0) \
            if run["trace"] else run["seconds"]
        client = StreamClient(host, port)
        got = drive(client, schedule, cfg, tr, seconds, profiler)
        drain(client, got["records"], got["t_open"],
              got["traced"][1] if profiler else got["t_close"],
              tr.get("drain_s", 10.0))
        if profiler:
            # the engine's tracer stays on through the drain, so that a
            # request sent in the traced stretch is seen queued after it
            profiler.join()
            engine_trace.uninstall()
            got["spans_end"] = time.perf_counter()
            spans = [(e.name, e.ts, e.ts + (e.dur_s or 0.0), e.rid,
                      e.meta or {}) for e in tracer.recorder.snapshot()]
        after = engine_counters(engine)
        client.abandon()
        storage = device.storage_census(jax, cfg["storage"])
        say("[storage] " + json.dumps(storage))
    finally:
        front.shutdown()
        engine.shutdown(drain=False)
    mem = device.memory(jax, 1)

    def free():
        """Drop the program's state and wait until the device has it back:
        the front's connection threads hold the engine until they have
        seen their sockets closed, and the reference needs the room."""
        nonlocal model, engine, front
        model = engine = front = None
        held = mem[0]["bytes_in_use"]
        deadline = time.perf_counter() + 120.0
        while held is not None:
            gc.collect()
            now = jax.devices()[0].memory_stats().get("bytes_in_use")
            if now is None or now < held // 4:
                return
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    "the program's state was not freed: %d of %d bytes "
                    "still in use" % (now, held))
            time.sleep(0.2)

    got.update(schedule=schedule, before=before, after=after, memory=mem,
               spans=spans, syncs=profiler.syncs if profiler else None,
               storage=storage,
               free=free, seconds=seconds,
               setup_parts={"build_s": t_built - t0,
                            "compile_and_warm_s": t_warm - t_built,
                            "warmup_traffic_s": tr["warmup_s"]})
    return got


class Refused(SystemExit):
    """The run cannot report honestly: exit code 3 and no result line."""

    def __init__(self, why: str):
        print("benchmark: run refused: " + why, file=sys.stderr, flush=True)
        super().__init__(3)


def summarize(run, got) -> dict:
    """The counts behind the client's metrics, printed (the metrics
    themselves are read by their readers); refuses a run that compiled in
    the window or whose generator ran late."""
    tr, recs = run["traffic"], got["records"]
    t0, t1 = got["t_open"], got["t_close"]
    length = t1 - t0
    worst = 1e3 * (length + tr.get("drain_s", 10.0))
    cnt = window.counts(recs, t0, t1)
    due = [r for r in recs if t0 <= r["due"] < t1]
    say("[traffic] window %.3f s; due in window: %s; sent in all: %s"
        % (length, traffic_mod.offered(due), traffic_mod.offered(recs)))
    tokens = window.tokens_in_window(recs, t0, t1)
    ttft = window.ttft_ms(recs, t0, t1, worst)
    tpot = window.tpot_ms(recs, t0, t1, worst)
    if ttft:
        say("[ttft] n=%d p50=%.2f p90=%.2f max=%.2f ms; mean of worst "
            "fifth %.2f" % (len(ttft), window.percentile(ttft, 0.5),
                            window.percentile(ttft, 0.9), max(ttft),
                            _tail_mean(ttft, 0.2)))
    if tpot:
        say("[tpot] n=%d p50=%.3f p90=%.3f max=%.3f ms; mean %.3f"
            % (len(tpot), window.percentile(tpot, 0.5),
               window.percentile(tpot, 0.9), max(tpot),
               sum(tpot) / len(tpot)))
    say("[tokens] %d token lines in the window, %.3f tokens/s; requests "
        "ended in window %d, in flight at close %d"
        % (tokens, tokens / length,
           sum(1 for r in recs if r["done"] is not None
               and t0 <= r["done"] < t1),
           sum(1 for r in recs if r["done"] is None or r["done"] >= t1)))
    late = window.lateness_ms(recs, t0, t1)
    late_p90 = window.percentile(late, 0.9) if late else 0.0
    say("[generator] lateness p90 %.3f ms, max %.3f ms over %d sends"
        % (late_p90, max(late) if late else 0.0, len(late)))
    before, after = got["before"], got["after"]
    say("[counters] compiles %s -> %s; recoveries %d; preempts %d; "
        "requests_failed %d; ticks_stalled %d"
        % (before["compiles"], after["compiles"],
           after["recoveries"] - before["recoveries"],
           after["preempts"] - before["preempts"],
           after["requests_failed"] - before["requests_failed"],
           after["ticks_stalled"] - before["ticks_stalled"]))
    if after["compiles"] != before["compiles"]:
        raise Refused("a program compiled inside the window: %s -> %s"
                      % (before["compiles"], after["compiles"]))
    if late_p90 > tr["max_lateness_p90_ms"]:
        raise Refused("the generator ran late: p90 %.1f ms over the %s ms "
                      "the traffic file allows" % (
                          late_p90, tr["max_lateness_p90_ms"]))
    failed = cnt["failed"] + int(after["recoveries"] - before["recoveries"])
    return {"statistics": {"tokens_per_s": tokens / length},
            "attempted": cnt["attempted"], "failed": failed}


def _tail_mean(values, share: float) -> float:
    vals = sorted(values)
    k = max(1, int(round(len(vals) * share)))
    return sum(vals[-k:]) / k


def compare(run, got) -> dict:
    from . import correct
    return correct.compare_serving(run["cfg"], run["seed"], got["schedule"],
                                   got["records"],
                                   run["traffic"]["check_requests"],
                                   got["storage"])
