"""Request-scoped tracing + the tick flight recorder.

The serving stack has aggregate metrics (``serving.metrics``) and a
lock-free health heartbeat (``serving.supervisor``) — this module is the
third observability leg: WHERE one request's latency went inside a
tick, and WHAT happened in the moments before a wedge.  Two pieces:

- :class:`FlightRecorder` — a bounded, lock-light ring buffer of
  :class:`TraceEvent` records.  Overflow evicts the oldest event and is
  itself observable (``dropped``, surfaced by the engine as the
  ``serving_trace_events_dropped_total`` counter), so a recorder can run
  forever on a production engine without growing.
- :class:`Tracer` — the emitter the instrumented code paths talk to:
  ``span(name)`` context managers for the tick phases (govern / admit /
  prefill / decode step / sample / deliver / observe / journal) and
  ``submit()``'s wait for the engine lock, and ``instant(name)`` marks for the
  request lifecycle (QUEUED→PREFILLING→DECODING→terminal, plus the
  PREEMPTED detour), compile events, fault injections, recoveries,
  shed decisions, supervisor stall/restart actions, and the
  degradation ladder's scheduler decisions (``sched.preempt`` /
  ``sched.resume`` / ``sched.degrade`` / ``sched.restore`` — every
  overload move lands in the ring with its tick, docs/DESIGN.md §5j).

Tracing OFF is a module-level no-op on the hot path — the same pattern
as the fault plane (``serving.faults``): call sites check one module
global against ``None`` (or call :func:`instant`, which does exactly
that), so the decode tick pays nothing and the ``tools/analysis``
host-sync rule stays clean when no tracer is installed.

**Spans time the HOST.**  An async decode dispatch returns before the
device finishes, so a phase span brackets python work plus whatever
sync the phase already contains (the per-tick token download is one).
Beside its length a span says how long its THREAD ran: ``cpu_s`` in its
meta is the calling thread's CPU time over the block, so ``dur_s -
cpu_s`` is what the thread waited (for the device, the interpreter
lock, a lock, a core).
Device time is read from the profiler's device trace, never from a
span — and so that the two can be read together, every span is ALSO a
``jax.profiler.TraceAnnotation`` of the same name (meta as keyword
stats): a profile anyone takes with ``jax.profiler`` while a tracer is
installed holds ``tick``, ``tick.*`` and ``submit.lock_wait`` in its
``/host:`` plane, on the trace's own clock, beside the device
operations (whose ``op_name`` carries the module tree: ``Layer.__call__``
runs under ``jax.named_scope``).  With no profiler session an annotation
costs one flag test.

Export: :func:`export_chrome_trace` converts a recorder snapshot to
Chrome/Perfetto trace-event JSON — one track per request (lifecycle
spans closed by the terminal event) and one per tick phase — through
the shared ``profiler.visual.chrome_trace_json`` writer.  The engine
wraps it as ``ServingEngine.export_chrome_trace()`` and serves
``GET /debug/trace?rid=<id>`` / ``GET /debug/flightrec``; the
supervisor dumps the recorder tail into ``EngineHealth`` on every
stall/restart so a post-mortem ships its own timeline (docs/DESIGN.md
§5g).
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import List, Optional

from jax.profiler import TraceAnnotation

from ..core.errors import InvalidArgumentError, PreconditionNotMetError
from ..profiler.visual import chrome_trace_json

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):     # not Linux: no per-thread usage
    resource = None

__all__ = ["TraceEvent", "FlightRecorder", "Tracer", "active", "install",
           "uninstall", "tracing", "instant", "thread_switches",
           "export_chrome_trace",
           "to_chrome_events", "LIFECYCLE_EVENTS", "TERMINAL_EVENTS"]

# the request-lifecycle event names (engine-emitted): non-terminal marks
# OPEN a lifecycle phase on the request's export track; terminal marks
# close it.  Everything else is a tick phase span or a point event
# (compile / fault.injected / recovery / shed / stall / restart, and
# the §5m durability plane's journal.error / journal.truncated /
# journal.checkpoint / spill.error / engine.restore / req.deferred
# marks — the chaos harness reconciles fault injections against the
# journal.error/spill.error counts exactly).
LIFECYCLE_EVENTS = {
    "req.queued": "QUEUED",
    "req.prefilling": "PREFILLING",
    "req.decoding": "DECODING",
}
TERMINAL_EVENTS = frozenset((
    "req.done", "req.cancelled", "req.expired", "req.failed",
    "req.aborted",
))


class TraceEvent:
    """One recorded event.  ``dur_s`` is None for instant marks; spans
    carry their duration.  ``rid`` ties an event to a request (None for
    engine-/tick-scoped events); ``meta`` is a small JSON-safe dict."""

    __slots__ = ("ts", "name", "rid", "dur_s", "meta")

    def __init__(self, ts, name, rid=None, dur_s=None, meta=None):
        self.ts = ts
        self.name = name
        self.rid = rid
        self.dur_s = dur_s
        self.meta = meta

    def to_dict(self) -> dict:
        out = {"ts": self.ts, "name": self.name}
        if self.rid is not None:
            out["rid"] = self.rid
        if self.dur_s is not None:
            out["dur_s"] = self.dur_s
        if self.meta:
            out["meta"] = self.meta
        return out

    def __repr__(self):  # debugging/pytest -v readability
        return "TraceEvent(%r, ts=%.6f%s%s)" % (
            self.name, self.ts,
            "" if self.rid is None else ", rid=%r" % (self.rid,),
            "" if self.dur_s is None else ", dur_s=%.6f" % self.dur_s)


class FlightRecorder:
    """Bounded ring buffer of trace events.

    ``capacity`` bounds memory whatever the traffic; overflow evicts the
    OLDEST event (a flight recorder keeps the moments before the crash,
    not the takeoff) and is counted in ``dropped`` so eviction is
    observable, never silent.  Lock-light: one short mutex around the
    deque append — no allocation beyond the event itself, no host
    sync — cheap enough for the tick path when tracing is on, and the
    whole structure is simply never touched when tracing is off."""

    def __init__(self, capacity: int = 4096):
        if int(capacity) < 1:
            raise InvalidArgumentError(
                "FlightRecorder needs capacity >= 1, got %r"
                % (capacity,))
        self.capacity = int(capacity)
        self._buf: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._total = 0

    def append(self, event: TraceEvent) -> None:
        with self._lock:
            self._buf.append(event)
            self._total += 1

    @property
    def total_events(self) -> int:
        """Events ever appended (retained + dropped)."""
        return self._total

    @property
    def dropped(self) -> int:
        """Events evicted by ring overflow — the engine mirrors this
        into ``serving_trace_events_dropped_total``."""
        with self._lock:
            return self._total - len(self._buf)

    def __len__(self) -> int:
        return len(self._buf)

    def snapshot(self) -> List[TraceEvent]:
        """The retained events, oldest first (a copy)."""
        with self._lock:
            return list(self._buf)

    def tail_dicts(self, n: int = 64) -> List[dict]:
        """The last ``n`` events as JSON-safe dicts — the post-mortem
        dump the supervisor attaches to ``EngineHealth``."""
        with self._lock:
            evs = list(self._buf)[-int(n):]
        return [e.to_dict() for e in evs]


class _Span:
    """The span context manager ``Tracer.span`` hands out: times the
    block on the tracer's clock, and on the calling thread's CPU clock
    beside it, and records ONE complete event at exit (start timestamp
    + duration, ``cpu_s`` in its meta), so a span costs four clock reads
    and one ring append — plus the ``TraceAnnotation`` twin that puts
    the same span into a running ``jax.profiler`` trace (one flag test
    when no profiler session is on).  ``dur_s - cpu_s`` is the time the
    thread neither ran nor could.  ``cpu_s`` rides in the meta, not in a
    field of its own: every consumer of an event (the exports, the
    benchmark's readers) is handed the meta already."""

    __slots__ = ("_tr", "_name", "_rid", "_meta", "_t0", "_cpu0", "_ann")

    def __init__(self, tr, name, rid, meta):
        self._tr = tr
        self._name = name
        self._rid = rid
        self._meta = meta

    def set(self, **meta) -> None:
        """Add what the block learned while it ran (how many slots were
        live, how many requests the tick admitted) to the span's meta
        and to its annotation's stats."""
        self._meta.update(meta)
        self._ann.set_metadata(**meta)

    def __enter__(self):
        stats = self._meta if self._rid is None \
            else dict(self._meta, rid=str(self._rid))
        self._ann = TraceAnnotation(self._name, **stats)
        self._ann.__enter__()
        self._t0 = self._tr._clock()
        self._cpu0 = self._tr._cpu_clock()
        return self

    def __exit__(self, *exc):
        tr = self._tr
        # the CPU readings inside the wall readings: cpu_s <= dur_s
        cpu = tr._cpu_clock() - self._cpu0
        dur = tr._clock() - self._t0
        self.set(cpu_s=cpu)
        self._ann.__exit__(*exc)
        tr._emit(TraceEvent(self._t0, self._name, self._rid, dur,
                            self._meta))
        return False


class Tracer:
    """The emitter instrumented code talks to; owns one
    :class:`FlightRecorder`.

    ``clock`` defaults to ``time.perf_counter`` — ALL trace timestamps
    live in this one clock domain, so cross-event ordering is
    meaningful even on engines driven by an injected deadline clock.
    ``cpu_clock`` defaults to ``time.thread_time``, the CALLING thread's
    CPU time: only differences taken on one thread mean anything."""

    def __init__(self, capacity: int = 4096, clock=None, cpu_clock=None):
        self.recorder = FlightRecorder(capacity)
        self._clock = clock if clock is not None else time.perf_counter
        self._cpu_clock = cpu_clock if cpu_clock is not None \
            else time.thread_time
        self._ticks = 0

    def now(self) -> float:
        """A reading of the TRACER's clock — the domain every event
        timestamp lives in.  Post-mortem dumps stamp this alongside the
        engine-clock ``at`` so consumers can align the dumped events'
        ``ts`` with the dump moment across the two clock domains."""
        return self._clock()

    def cpu_now(self) -> float:
        """A reading of the calling thread's CPU clock, the one a
        span's ``cpu_s`` is taken on."""
        return self._cpu_clock()

    @property
    def tick(self) -> int:
        """The CURRENT tick number (0 before the first traced tick) —
        the join key the structured log (serving/log.py) stamps on
        every event so log lines and flight-recorder timelines align
        by number."""
        return self._ticks

    def next_tick(self) -> int:
        """The engine's tick sequence number under THIS tracer (restarts
        at 1 with a fresh tracer — tick numbering is a trace-lifetime
        concept).  Single-writer by construction: only the ticking
        thread calls it, under the engine lock — the recorder behind
        ``_emit`` keeps its own mutex for the multi-writer side."""
        self._ticks += 1
        return self._ticks

    def instant(self, name: str, rid=None, **meta) -> None:
        """Record a point event (lifecycle transition, compile, fault
        injection, recovery, shed, stall, restart)."""
        self._emit(TraceEvent(self._clock(), name, rid, None,
                              meta or None))

    def span(self, name: str, rid=None, **meta) -> _Span:
        """Context manager timing one tick phase (or any block); the
        span it yields takes late meta through ``set(**meta)``."""
        return _Span(self, name, rid, meta)

    def _emit(self, event: TraceEvent) -> None:
        self.recorder.append(event)


# -- module-level activation (the fault-plane pattern) --------------------
# ONE global tracer: the hot-path cost of tracing-off is a single
# is-None test in instant()/active(), nothing else.
_TRACER: Optional[Tracer] = None


def active() -> Optional[Tracer]:
    """The installed tracer, or None when tracing is off."""
    return _TRACER


def install(tracer: Tracer) -> Tracer:
    """Activate ``tracer`` process-wide; returns it.  Refuses to stack —
    two tracers would split one engine's timeline across two rings."""
    global _TRACER
    if _TRACER is not None:
        raise PreconditionNotMetError(
            "a Tracer is already installed; uninstall() it first (one "
            "timeline per process — traces do not compose across "
            "tracers)")
    _TRACER = tracer
    return tracer


def uninstall() -> None:
    """Deactivate tracing (idempotent).  The last tracer's recorder
    stays readable — the engine keeps a reference for export and
    post-mortem dumps."""
    global _TRACER
    _TRACER = None


@contextlib.contextmanager
def tracing(tracer: Tracer):
    """``with trace.tracing(t):`` — install for the block, always
    uninstall after, so a failing test cannot leak a tracer into the
    next one."""
    install(tracer)
    try:
        yield tracer
    finally:
        uninstall()


def instant(name: str, rid=None, **meta) -> None:
    """The module-level emission seam call sites use: a no-op unless a
    tracer is installed."""
    t = _TRACER
    if t is not None:
        t.instant(name, rid=rid, **meta)


def thread_switches():
    """``(nvcsw, nivcsw)``: the context switches of the CALLING thread
    so far, those it made itself (it blocked: on the interpreter lock,
    a lock, the device) and those made for it (its core was taken).
    None where the platform counts none by thread.  One system call."""
    if resource is None:
        return None
    ru = resource.getrusage(_RUSAGE_THREAD)
    return ru.ru_nvcsw, ru.ru_nivcsw


# -- Chrome/Perfetto export ----------------------------------------------

def to_chrome_events(events: List[TraceEvent]) -> List[dict]:
    """Transform a recorder snapshot into Chrome trace-event dicts.

    Layout: pid 0 holds one track (tid) per tick-phase/point-event name;
    pid 1 holds one track per request.  Request lifecycle marks become
    complete ("X") spans closed by the NEXT transition — the terminal
    mark closes the last one and lands as its own instant — so a
    drained/shut-down engine exports timelines with no open spans; a
    request still live at export time gets its trailing span flagged
    ``"open": true`` instead of silently truncated.  Events are sorted
    by timestamp per track (monotonic within every (pid, tid))."""
    evs = sorted(events, key=lambda e: e.ts)
    out: List[dict] = []
    out.append({"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                "args": {"name": "tick phases"}})
    out.append({"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
                "args": {"name": "requests"}})
    phase_tids: dict = {}

    def phase_tid(name):
        tid = phase_tids.get(name)
        if tid is None:
            tid = len(phase_tids)
            phase_tids[name] = tid
            out.append({"name": "thread_name", "ph": "M", "pid": 0,
                        "tid": tid, "args": {"name": name}})
        return tid

    req_tids: dict = {}

    def req_tid(rid_key):
        tid = req_tids.get(rid_key)
        if tid is None:
            tid = len(req_tids)
            req_tids[rid_key] = tid
            out.append({"name": "thread_name", "ph": "M", "pid": 1,
                        "tid": tid,
                        "args": {"name": "request %s" % (rid_key,)}})
        return tid

    by_rid: dict = {}
    for e in evs:
        if e.name in LIFECYCLE_EVENTS or e.name in TERMINAL_EVENTS:
            by_rid.setdefault(str(e.rid), []).append(e)
            continue
        args = dict(e.meta or {})
        if e.rid is not None:
            args["rid"] = e.rid if isinstance(e.rid, (str, int, float)) \
                else str(e.rid)
        if e.dur_s is not None:
            out.append({"name": e.name, "ph": "X", "cat": "phase",
                        "pid": 0, "tid": phase_tid(e.name),
                        "ts": e.ts * 1e6,
                        "dur": max(e.dur_s, 0.0) * 1e6, "args": args})
        else:
            out.append({"name": e.name, "ph": "i", "s": "g",
                        "cat": "event", "pid": 0,
                        "tid": phase_tid(e.name), "ts": e.ts * 1e6,
                        "args": args})
    end_ts = evs[-1].ts if evs else 0.0
    for rid_key, revs in by_rid.items():
        tid = req_tid(rid_key)
        for i, ev in enumerate(revs):
            nxt = revs[i + 1] if i + 1 < len(revs) else None
            args = dict(ev.meta or {})
            if ev.name in TERMINAL_EVENTS:
                out.append({"name": ev.name.split(".", 1)[1].upper(),
                            "ph": "i", "s": "t", "cat": "lifecycle",
                            "pid": 1, "tid": tid, "ts": ev.ts * 1e6,
                            "args": args})
                continue
            close = end_ts if nxt is None else nxt.ts
            if nxt is None:
                # no terminal mark reached the recorder: the request is
                # still live (or its terminal was evicted) — say so
                # rather than faking a closed span
                args["open"] = True
            out.append({"name": LIFECYCLE_EVENTS[ev.name], "ph": "X",
                        "cat": "lifecycle", "pid": 1, "tid": tid,
                        "ts": ev.ts * 1e6,
                        "dur": max(close - ev.ts, 0.0) * 1e6,
                        "args": args})
    # monotonic per track: metadata ("M", no ts) sorts first
    out.sort(key=lambda d: (d["pid"], d["tid"], d.get("ts", -1.0)))
    return out


def export_chrome_trace(events: List[TraceEvent],
                        path: Optional[str] = None) -> str:
    """Serialize ``events`` as Chrome trace-event JSON (returned; also
    written to ``path`` when given) through the shared
    ``profiler.visual.chrome_trace_json`` writer — the same format the
    training-side op-table export emits, so one viewer reads both."""
    return chrome_trace_json(to_chrome_events(events), path=path)
