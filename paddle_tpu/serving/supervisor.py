"""Tick supervision: the engine's health record and its watchdog.

The serving engine's background loop has two failure shapes the loop
itself cannot report: the thread DIES (an exception that escapes the
tick's recovery — the loop is gone, nothing ticks again) and the tick
WEDGES (a dispatch that hangs without raising — the loop is alive but
frozen, holding the engine lock).  Both are invisible from inside; both
need an observer with its own thread and NO dependency on the engine
lock.  That observer is :class:`Supervisor`:

- **dead loop**: the engine's thread handle exists but the thread is
  not alive while the engine was neither stopped nor drained — the
  supervisor restarts the loop (``ServingEngine.restart_loop``) and
  counts it in ``serving_engine_restarts_total``;
- **stalled tick**: a tick started more than ``stall_timeout_s`` ago
  and never finished — the supervisor opens a STALL episode (counted
  once per episode in ``serving_ticks_stalled_total``, closed by the
  tick eventually finishing), which flips ``health()`` — and therefore
  ``GET /healthz`` — to unhealthy for the duration.  A wedged python
  thread cannot be killed, so the supervisor's job here is honest
  visibility plus a restart the moment the thread dies or unwedges.

:class:`EngineHealth` is the lock-free heartbeat record behind
``ServingEngine.health()``: single-writer fields (the tick thread
writes under the engine lock; the supervisor only opens stall
episodes), read without any lock on purpose — health is exactly the
question you ask WHILE the engine lock is wedged.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from ..core.errors import InvalidArgumentError
from . import trace

__all__ = ["EngineHealth", "Supervisor", "FleetSupervisor"]


class EngineHealth:
    """Mutable heartbeat/post-mortem record for one engine.

    Plain attributes, no lock: every field is written by a single
    writer (the ticking thread under the engine lock, or the supervisor
    for ``stall_open``/``stalls``) and read lock-free by ``health()``
    and the watchdog — a torn read costs at worst one poll interval of
    staleness, never a deadlock against a wedged tick."""

    def __init__(self):
        self.tick_started_at: Optional[float] = None
        self.tick_finished_at: Optional[float] = None
        self.ticks_total = 0
        self.last_error: Optional[str] = None
        self.last_error_at: Optional[float] = None
        self.last_error_kind: Optional[str] = None
        self.restarts = 0
        self.recoveries = 0
        self.requests_recovered = 0
        self.restores = 0
        self.last_restore_s: Optional[float] = None
        self.stalls = 0
        self.stall_open = False
        # post-mortem timeline: the flight recorder's tail, attached by
        # the supervisor on stall/restart and by the dying loop itself
        # ({"reason", "at", "events"} — already JSON-safe dicts)
        self.flight_dump: Optional[dict] = None

    # -- written by the ticking thread (under the engine lock) -----------
    def note_tick_start(self, now: float) -> None:
        self.tick_started_at = now

    def note_tick_end(self, now: float) -> None:
        self.tick_finished_at = now
        self.ticks_total += 1
        self.stall_open = False  # a finished tick closes any episode

    def note_error(self, now: float, exc: BaseException,
                   kind: str) -> None:
        """Record the last failure for post-mortems: the step error a
        recovery handled, or the loop-killing error ``_loop`` caught —
        either way ``health()`` carries WHAT and WHEN, so a parked loop
        is never a debugger-only mystery."""
        self.last_error = "%s: %s" % (type(exc).__name__, str(exc)[:300])
        self.last_error_at = now
        self.last_error_kind = kind

    def note_recovery(self, resubmitted: int) -> None:
        self.recoveries += 1
        self.requests_recovered += resubmitted

    def note_restore(self, duration_s: float) -> None:
        """A journal restore completed on this engine (docs §5m): the
        count and the last restore's wall time ride every health
        snapshot, so a probe can tell "slow because it just adopted a
        journal" from "slow, period"."""
        self.restores += 1
        self.last_restore_s = duration_s

    def note_restart(self, now: float) -> None:
        self.restarts += 1
        self.stall_open = False  # the wedged loop is gone; fresh start

    def note_flight_dump(self, now: float, reason: str, events: list,
                         trace_now: Optional[float] = None) -> None:
        """Attach the flight recorder's tail (JSON-safe event dicts):
        every stall, watchdog restart, and loop-killing error ships the
        timeline that led up to it — one field write, so the lock-free
        read discipline holds (a torn read sees the previous dump,
        never a mix).  ``at`` is in the ENGINE clock domain (consistent
        with every other timestamp in this snapshot); the events' ``ts``
        live in the TRACER's clock, so ``trace_now`` — the tracer clock
        at dump time — is stamped alongside to let a consumer align
        the two."""
        self.flight_dump = {"reason": reason, "at": now,
                            "trace_now": trace_now, "events": events}

    # -- written by the supervisor ---------------------------------------
    def open_stall(self) -> bool:
        """Open a stall episode; True only on the OPENING observation
        (the caller counts episodes, not polls)."""
        if self.stall_open:
            return False
        self.stall_open = True
        self.stalls += 1
        return True

    def tick_busy(self) -> bool:
        """A tick started and has not finished."""
        return self.tick_started_at is not None and (
            self.tick_finished_at is None
            or self.tick_finished_at < self.tick_started_at)

    def snapshot(self) -> dict:
        return {
            "ticks_total": self.ticks_total,
            "last_tick_started_at": self.tick_started_at,
            "last_tick_finished_at": self.tick_finished_at,
            "last_error": self.last_error,
            "last_error_at": self.last_error_at,
            "last_error_kind": self.last_error_kind,
            "restarts": self.restarts,
            "recoveries": self.recoveries,
            "requests_recovered": self.requests_recovered,
            "restores": self.restores,
            "last_restore_s": self.last_restore_s,
            "ticks_stalled": self.stalls,
            "flight_dump": self.flight_dump,
        }


class Supervisor:
    """Watchdog over one :class:`~.engine.ServingEngine`.

    ``check_once()`` is the whole policy — one sweep, returns the list
    of actions taken (``"stall-detected"``, ``"loop-restarted"``) so
    tests drive supervision deterministically with an injected clock.
    ``start()`` runs the same sweep from an owned daemon thread every
    ``poll_interval_s`` for real serving.  The supervisor NEVER takes
    the engine lock: detection reads the lock-free health record, and
    the only mutation it performs — restarting a DEAD loop — goes
    through ``restart_loop()``, which can take the lock safely because
    a dead thread by definition is not holding it."""

    def __init__(self, engine, stall_timeout_s: float = 5.0,
                 poll_interval_s: Optional[float] = None, clock=None):
        if not float(stall_timeout_s) > 0.0:
            raise InvalidArgumentError(
                "stall_timeout_s must be > 0, got %r" % (stall_timeout_s,))
        self.engine = engine
        self.stall_timeout_s = float(stall_timeout_s)
        self.poll_interval_s = (max(0.005, self.stall_timeout_s / 4.0)
                                if poll_interval_s is None
                                else float(poll_interval_s))
        # default to the ENGINE's clock, not time.monotonic: heartbeat
        # timestamps are stamped in the engine's clock domain, and
        # stall math across two time bases would misfire (an engine
        # with an injected test clock would look permanently wedged)
        self._clock = clock if clock is not None \
            else getattr(engine, "_clock", time.monotonic)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- the one supervision sweep ---------------------------------------
    def check_once(self) -> List[str]:
        """Detect a stalled tick and/or a dead loop; return the actions
        taken this sweep (possibly empty)."""
        actions: List[str] = []
        eng = self.engine
        health = eng._health
        now = self._clock()
        if health.tick_busy() and \
                now - health.tick_started_at >= self.stall_timeout_s:
            if health.open_stall():
                eng._note_stall()
                actions.append("stall-detected")
        thread = eng._thread
        if thread is not None and not thread.is_alive() \
                and not eng._stop.is_set() and not eng.draining:
            if eng.restart_loop():
                actions.append("loop-restarted")
        if actions:
            # every supervised incident ships its own timeline: dump
            # the flight recorder's tail into the health record the
            # moment a stall opens or a dead loop is restarted, so
            # GET /healthz IS the post-mortem (no-op when no tracer
            # was ever active on the engine)
            tr = trace.active() or getattr(eng, "_tracer", None)
            if tr is not None:
                health.note_flight_dump(now, "+".join(actions),
                                        tr.recorder.tail_dicts(),
                                        trace_now=tr.now())
        return actions

    # -- owned watchdog thread -------------------------------------------
    def start(self) -> "Supervisor":
        with self._lock:
            if self._thread is None:
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="serving-engine-supervisor",
                    daemon=True)
                self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            self.check_once()
            self._stop.wait(self.poll_interval_s)

    def stop(self) -> None:
        with self._lock:
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=10.0)
                self._thread = None

    def is_running(self) -> bool:
        return self._thread is not None


class FleetSupervisor:
    """Per-engine supervision fanned in at fleet scope (docs §5o).

    One :class:`Supervisor` per live engine — created as the fleet
    spawns engines, dropped as they retire or die — plus the one
    escalation a single-engine watchdog cannot make: an engine whose
    tick has been wedged past ``escalate_timeout_s`` (a python thread
    cannot be killed, so the single-engine policy stops at honest
    visibility) is declared dead TO THE FLEET via
    ``fleet.hard_abandon``, which migrates its live requests onto
    survivors.  Detection is the same lock-free health-record read the
    per-engine watchdog uses; each sub-supervisor keeps its engine's
    own clock domain, so injected test clocks supervise
    deterministically.

    ``check_once()`` is again the whole policy: one sweep over every
    active/draining engine, returning ``{engine_id: [actions...]}``
    (the per-engine actions plus ``"engine-abandoned"`` on
    escalation).  ``start()`` runs it from an owned daemon thread for
    real serving — out-of-band on purpose, since a wedged engine tick
    wedges the fleet's own pump loop with it."""

    def __init__(self, fleet, stall_timeout_s: float = 5.0,
                 escalate_timeout_s: Optional[float] = None,
                 poll_interval_s: Optional[float] = None):
        if not float(stall_timeout_s) > 0.0:
            raise InvalidArgumentError(
                "stall_timeout_s must be > 0, got %r"
                % (stall_timeout_s,))
        self.fleet = fleet
        self.stall_timeout_s = float(stall_timeout_s)
        self.escalate_timeout_s = (4.0 * self.stall_timeout_s
                                   if escalate_timeout_s is None
                                   else float(escalate_timeout_s))
        if self.escalate_timeout_s < self.stall_timeout_s:
            raise InvalidArgumentError(
                "escalate_timeout_s (%r) must be >= stall_timeout_s "
                "(%r): abandonment is the step AFTER stall detection"
                % (self.escalate_timeout_s, self.stall_timeout_s))
        self.poll_interval_s = (max(0.005, self.stall_timeout_s / 4.0)
                                if poll_interval_s is None
                                else float(poll_interval_s))
        self._subs: Dict[object, Supervisor] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def check_once(self) -> Dict[object, List[str]]:
        """One fan-in sweep: sync the sub-supervisor set with the
        fleet's live engines, run each engine's own sweep, escalate
        wedges that outlived ``escalate_timeout_s``."""
        out: Dict[object, List[str]] = {}
        states = self.fleet.engine_states()
        engines = self.fleet.engines()
        for eid in list(self._subs):
            if states.get(eid) not in ("active", "draining"):
                del self._subs[eid]
        for eid, eng in engines.items():
            if states.get(eid) not in ("active", "draining"):
                continue
            sup = self._subs.get(eid)
            if sup is None:
                sup = self._subs[eid] = Supervisor(
                    eng, stall_timeout_s=self.stall_timeout_s)
            actions = sup.check_once()
            h = eng._health
            now = sup._clock()
            if h.stall_open and h.tick_busy() \
                    and now - h.tick_started_at \
                    >= self.escalate_timeout_s:
                wedged_s = now - h.tick_started_at
                self.fleet.hard_abandon(
                    eid, error="tick wedged %.3fs — supervisor "
                               "escalation" % wedged_s)
                actions = list(actions) + ["engine-abandoned"]
                del self._subs[eid]
            if actions:
                out[eid] = actions
        return out

    # -- owned watchdog thread (same shape as Supervisor) -----------------
    def start(self) -> "FleetSupervisor":
        with self._lock:
            if self._thread is None:
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="serving-fleet-supervisor",
                    daemon=True)
                self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            self.check_once()
            self._stop.wait(self.poll_interval_s)

    def stop(self) -> None:
        with self._lock:
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=10.0)
                self._thread = None

    def is_running(self) -> bool:
        return self._thread is not None
