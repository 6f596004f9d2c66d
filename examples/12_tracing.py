"""Example 12: request-scoped tracing + the tick flight recorder (§5g).

Example 11 broke the serving stack on purpose and watched it recover;
this one watches WHERE the time goes and WHAT happened — the
observability leg (docs/DESIGN.md §5g):

1. **tracing**: ``engine.start_trace()`` installs a bounded flight
   recorder; every tick runs as a numbered span with per-phase children
   (govern / admit / prefill / decode / sample / deliver / observe /
   journal) that say what the tick did (``queued``, ``admitted``,
   ``finished`` on ``tick``; ``live`` of ``slots`` on ``tick.decode``;
   ``bucket`` beside ``prompt_tokens`` on ``tick.prefill``), and
   lifecycle transitions, compile events, fault injections, recoveries
   and sheds land in the ring.  Tracing off is a module-level no-op on
   the hot path;
2. **per-request timelines**: ``engine.request_trace(rid)`` — the
   ``GET /debug/trace?rid=`` body — shows one request's path, including
   the injection → recovery → completion sequence of a faulted run;
3. **Chrome export**: ``engine.export_chrome_trace(path)`` writes
   trace-event JSON (one track per request + per tick phase) that
   chrome://tracing / Perfetto load directly;
4. **the request's own timeline, tracer or not**: every terminal status
   (and the terminal ndjson line of ``POST /generate``) carries
   ``lock_wait_s``, how long ``submit()`` stood before the engine lock,
   which ``ttft_s``/``total_s`` leave out, and ``queue_wait_s``, from
   admission to its first slot;
5. **open the profile and see the tick**: while a tracer is installed
   every span is also a ``jax.profiler.TraceAnnotation``, so a profile
   anyone takes holds ``tick``, ``tick.*`` and ``submit.lock_wait`` in
   its host plane, on the trace's own clock, beside the device
   operations, whose ``op_name`` carries the module tree
   (``encoder/layers/0/self_attn/q_proj``, ``lm_head``, ``sample``);
6. **how long a span's thread ran, and what the front did with a
   token**: every span's meta carries ``cpu_s``, its thread's CPU time,
   so ``dur_s - cpu_s`` is what the thread waited (device, interpreter
   lock, a lock, a core); ``tick`` counts its thread's context switches
   (``nvcsw`` its own, ``nivcsw`` the machine's); and each
   ``POST /generate`` ends with ONE ``http.stream`` instant: the token
   lines it flushed, what they waited between the engine's put and the
   flush, and the handler thread's CPU time.

Run: python examples/12_tracing.py [--tokens 8]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import argparse
import glob
import json
import tempfile

import numpy as np

import paddle_tpu as pt
from paddle_tpu.models import TransformerLM
from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend, faults


def build_engine(model):
    return ServingEngine(model, max_len=128, slots=2, buckets=[64, 128],
                         max_queue=8, cache_layout="paged",
                         block_size=32, max_retries=4)


def run(engine, prompts, tokens):
    streams = [engine.submit(p, tokens, request_id="req-%d" % i)
               for i, p in enumerate(prompts)]
    while engine.pump(4):
        pass
    return [s.result(timeout_s=0) for s in streams]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8)
    args = ap.parse_args()

    pt.seed(0)
    model = TransformerLM(vocab_size=256, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=128,
                          max_position=256, causal=True, dropout=0.0)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 256, (n,)).astype("int32")
               for n in (20, 35, 28)]

    # -- trace a faulted run: the timeline carries its own post-mortem
    engine = build_engine(model)
    tracer = engine.start_trace(capacity=2048)
    spec = faults.FaultSpec("pool.step",
                            error=faults.TransientInjectedFault,
                            after=2, times=1)
    with faults.injected(faults.FaultPlane([spec])):
        statuses = run(engine, prompts, args.tokens)
    engine.stop_trace()
    print("states:", [st.state for st in statuses])
    events = tracer.recorder.snapshot()
    print("flight recorder: %d events (capacity %d, dropped %d)"
          % (len(events), tracer.recorder.capacity,
             tracer.recorder.dropped))
    by_name = {}
    for e in events:
        by_name[e.name] = by_name.get(e.name, 0) + 1
    print("event counts:", dict(sorted(by_name.items())))

    # -- one request's timeline (the GET /debug/trace?rid= body)
    recovered = [e.rid for e in events if e.name == "recovery.resubmit"]
    rid = recovered[0] if recovered else statuses[0].request_id
    tl = engine.request_trace(rid)
    print("timeline for %s:" % rid)
    for e in tl["events"]:
        print("  %-18s %s" % (e["name"],
                              "dur=%.1fus" % (e["dur_s"] * 1e6)
                              if "dur_s" in e else ""))

    # -- Chrome/Perfetto export (load in chrome://tracing)
    path = os.path.join(tempfile.mkdtemp(prefix="paddle_tpu_trace_"),
                        "serving_trace.json")
    engine.export_chrome_trace(path)
    doc = json.load(open(path))
    print("chrome trace: %d events -> %s" % (len(doc["traceEvents"]),
                                             path))

    # -- what the ticks did, from the spans' meta
    decode = [e.meta for e in events if e.name == "tick.decode"]
    ticks = [e.meta for e in events if e.name == "tick"]
    print("ticks: %d, admitted %d, finished %d; mean live rows %.2f of %d"
          % (len(ticks), sum(m["admitted"] for m in ticks),
             sum(m["finished"] for m in ticks),
             sum(m["live"] for m in decode) / len(decode),
             decode[0]["slots"]))

    # -- how long the ticking thread ran inside a tick, and its switches
    for e in [e for e in events if e.name == "tick"][:3]:
        print("tick %d: %.0f us long, %.0f us on the CPU; switched out "
              "%s time(s) by itself, %s by the machine"
              % (e.meta["tick"], e.dur_s * 1e6, e.meta["cpu_s"] * 1e6,
                 e.meta.get("nvcsw", "?"), e.meta.get("nivcsw", "?")))

    # -- the request's own timeline needs no tracer: it is on the status
    engine2 = build_engine(model)
    for st in run(engine2, prompts[:2], args.tokens):
        print("%s: lock_wait_s=%.6f queue_wait_s=%.6f ttft_s=%.6f "
              "(ttft_s runs from admission: add lock_wait_s)"
              % (st.request_id, st.lock_wait_s, st.queue_wait_s,
                 st.ttft_s))

    # -- open the profile and see the tick: the spans are in it
    import jax
    from jax.profiler import ProfileData
    profile_dir = tempfile.mkdtemp(prefix="paddle_tpu_profile_")
    engine2.start_trace(capacity=512)
    jax.profiler.start_trace(profile_dir)
    run(engine2, prompts[:1], args.tokens)
    jax.profiler.stop_trace()
    engine2.stop_trace()
    (pb,) = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("tick", "submit.")):
                        seen[ev.name] = seen.get(ev.name, 0) + 1
    print("annotations in the profile's host plane:",
          dict(sorted(seen.items())))

    # -- what the HTTP front did with a request's tokens: one instant
    import http.client
    front = ServingHTTPFrontend(engine2).start()
    tracer = engine2.start_trace(capacity=512)
    conn = http.client.HTTPConnection(*front.address)
    conn.request("POST", "/generate", json.dumps({
        "prompt": prompts[0].tolist(), "max_new_tokens": args.tokens,
        "request_id": "over-http"}))
    conn.getresponse().read()
    conn.close()
    engine2.stop_trace()
    front.shutdown()
    (ev,) = [e for e in tracer.recorder.snapshot()
             if e.name == "http.stream"]
    print("http.stream for %s: %d lines, put to flushed %.0f us a line "
          "(worst %.0f us), %.0f us of the handler's CPU"
          % (ev.rid, ev.meta["lines"],
             1e6 * ev.meta["lag_sum_s"] / ev.meta["lines"],
             1e6 * ev.meta["lag_max_s"], 1e6 * ev.meta["cpu_s"]))
    print("done.")


if __name__ == "__main__":
    main()
