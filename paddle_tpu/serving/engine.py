"""The serving engine: request lifecycle over the continuous-batching pool.

``inference.GenerationPool`` is the hardware-facing half of serving —
slots, paged blocks, one batched decode dispatch per step.  This module
is the half a server actually talks to: a scheduler that owns the
request LIFECYCLE (``QUEUED → PREFILLING → DECODING → {DONE, CANCELLED,
EXPIRED, FAILED}``), admission control, per-request deadlines, token
streaming, and the serving metrics a dashboard needs — the
framework-level analog of the reference's ``paddle/fluid/inference``
serving layer rebuilt over the TPU-native decode engine (PAPERS.md:
compiler-first O(1) autoregressive caching treats the cached step as a
component INSIDE a request scheduler; this is that scheduler).

Design points (docs/DESIGN.md §5c):

- **One tick, two drive modes.** A scheduling tick = deadline sweep +
  one batched ``pool.step()`` + gauge refresh.  ``pump(n)`` runs ticks
  inline (single-threaded, deterministic — what every tier-1 test
  uses); ``start()`` runs the SAME ``_tick`` in an owned
  background thread for real serving.  The modes share one code path,
  so they cannot diverge.
- **Fail-fast admission.** The wait queue is bounded (``max_queue``);
  an over-depth ``submit`` raises the typed, retryable
  :class:`QueueFullError` instead of buffering unboundedly —
  backpressure surfaces at the caller, where load shedding belongs.
- **Deadlines and cancellation free real resources.**  Expiry/cancel
  route through ``GenerationPool.cancel`` → ``release(slot)``: the slot
  and its paged KV blocks return to the allocator mid-generation
  (``cache_stats()`` returns to baseline — pinned by tests).
- **Metrics from the real path.** TTFT is observed by the pool's
  ``on_tokens`` hook at the actual first-token moment inside ``step()``;
  queue depth/occupancy are read per tick; the step loop reuses
  ``profiler.StepTimer`` for sustained tokens/s.
- **Request-level blast radius.** A failed ``pool.step()`` no longer
  fails every live request: prompt + committed tokens fully determine
  greedy decode state (the O(1)-cache contract, PAPERS.md), so
  ``_recover`` rebuilds the pool (same compiled executables, fresh
  caches/allocator) and resubmits each victim's prompt+committed
  tokens — greedy requests continue TOKEN-IDENTICALLY.  Retries are
  bounded per request (``max_retries``) and typed
  (``faults.classify_error``): permanent errors and exhausted budgets
  finalize FAILED carrying the retry count and root error.
- **Supervision surface.** Every tick stamps a lock-free heartbeat
  (``supervisor.EngineHealth``); ``health()`` reads it WITHOUT the
  engine lock (a wedged tick holds the lock — health is exactly what
  you ask during a wedge) and backs ``GET /healthz``.  The
  ``supervisor.Supervisor`` watchdog restarts a dead loop via
  ``restart_loop()`` and opens stall episodes past its
  ``stall_timeout_s``.
- **Deadline-aware shedding.** A ``deadline_s`` submit that cannot
  finish in time — given the live backlog and the OBSERVED tick rate —
  is shed at admission with the typed, retryable
  :class:`DeadlineUnattainableError` (carrying a ``retry_after_s``
  hint, mapped to HTTP 503 + Retry-After) instead of burning a slot on
  output its caller will throw away.
- **Traffic-grade scheduling, SLO-closed-loop.** Requests carry a
  ``priority`` class and an optional ``tenant`` fairness key; the
  pool admits by (priority, deadline, arrival) with per-tenant slot
  caps, and ``preempt()`` evicts a decoding victim by spilling its
  paged K/V to a host-RAM tier, to be resumed BYTE-identically (the
  docs/DESIGN.md §5j contract).  With ``degrade=True`` the SLO
  tracker's multi-window burn alert drives a degradation LADDER —
  preempt low-priority, reduce spec-K, tighten admission — stepping
  down while the alert burns and back up when it clears, with every
  decision emitted as a ``sched.*`` flight-recorder event and
  structured-log line so overload behavior is post-hoc auditable.
  Degraded is healthy: ``/healthz`` stays 200 and carries the level.
- **Crash durability.** With ``journal_path=`` every admission and
  each tick's committed-token batch land in an append-only CRC-framed
  write-ahead journal (``serving/journal.py``) whose header carries
  the pool's config fingerprint; ``checkpoint()`` compacts it to one
  snapshot record and ``restore(path)`` lets a FRESH process (or a
  second engine with the same weights) adopt it — spilled victims
  re-parked straight from the ``spill_tier="disk"`` directory, every
  other survivor resubmitted prompt+committed through the SAME
  ``_recover`` machinery — finishing every greedy survivor
  byte-identically with zero new compiles on warmed executables.
  While replaying the engine is RESTORING: ``/healthz`` 503 +
  Retry-After, submits deferred (never dropped).  The journal falls
  BEHIND under write faults (records stay pending), never wrong: a
  lost tail only re-decodes at restore (docs/DESIGN.md §5m).
- **Request-scoped tracing.** With a tracer installed
  (``start_trace()`` / ``serving.trace``) every tick runs inside a
  numbered span, lifecycle transitions / recoveries / sheds / compiles
  land in the bounded flight recorder, and
  ``export_chrome_trace()`` / ``request_trace()`` /
  ``flight_recorder()`` expose the timeline (docs/DESIGN.md §5g).
  Tracing off is a module-level no-op on the tick path.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..core.errors import (InvalidArgumentError, NotFoundError,
                           PreconditionNotMetError, UnavailableError)
from ..inference.generation import (DuplicateRequestError, GenerationPool,
                                    _SamplingConfig, tick_phase)
from ..profiler import StepTimer
from . import faults, trace
from . import log as slog
from .journal import (FingerprintMismatchError, JournalWriteError,
                      JournalWriter, read_journal, replay)
from .metrics import MetricsRegistry
from .stream import RequestState, ResponseStream, StreamStatus
from .supervisor import EngineHealth

__all__ = ["ServingEngine", "QueueFullError", "DeadlineUnattainableError",
           "AdmissionTightenedError", "PRIORITY_CLASSES"]

# named priority classes the HTTP schema (and convenience callers)
# accept; priorities are plain ints underneath — higher admits first,
# ties broken by deadline then arrival (docs/DESIGN.md §5j)
PRIORITY_CLASSES = {"low": -1, "normal": 0, "high": 1}


def _jsonable_rid(rid):
    """Request ids round-trip the journal as JSON values: ints and
    strings survive verbatim (numpy ints normalized) — everything else
    is rejected at the submit edge by ``_check_journal_rid``."""
    if isinstance(rid, np.integer):
        return int(rid)
    return rid


def _samp_json(cfg):
    """A resolved per-request sampling config as its journal/migration
    wire form — the 5-list ``[temperature, top_k, top_p, seed, draws]``
    (None passes through: a record written without per-request
    sampling replays greedy)."""
    if cfg is None:
        return None
    return [float(cfg.temperature), int(cfg.top_k), float(cfg.top_p),
            int(cfg.seed), int(cfg.draws)]


def _samp_from_json(val):
    """Inverse of :func:`_samp_json`; tolerates the 4-list form (no
    ``draws`` field) so wire records from the first per-request-sampling
    writers replay with a zero stream offset."""
    if val is None:
        return None
    return _SamplingConfig(
        float(val[0]), int(val[1]), float(val[2]), int(val[3]),
        int(val[4]) if len(val) > 4 else 0)


def _normalize_priority(priority) -> int:
    if isinstance(priority, str):
        if priority not in PRIORITY_CLASSES:
            raise InvalidArgumentError(
                "unknown priority class %r; named classes are %s, or "
                "pass an int (higher admits first)"
                % (priority, sorted(PRIORITY_CLASSES)))
        return PRIORITY_CLASSES[priority]
    if isinstance(priority, bool) or not isinstance(
            priority, (int, np.integer)):
        raise InvalidArgumentError(
            "priority must be an int or one of %s, got %r"
            % (sorted(PRIORITY_CLASSES), priority))
    return int(priority)


class QueueFullError(UnavailableError):
    """Admission rejected: the wait queue is at ``max_queue`` depth.
    Typed and RETRYABLE — the caller backs off and resubmits; the
    engine never buffers beyond its declared bound."""


class DeadlineUnattainableError(UnavailableError):
    """Admission rejected: given the current backlog and the observed
    per-tick decode rate, the request cannot finish inside its own
    ``deadline_s`` — admitting it would burn a slot on output the
    caller is contractually going to discard.  Typed and RETRYABLE;
    ``retry_after_s`` estimates when the backlog will have drained
    enough to make the same deadline feasible (the HTTP front end maps
    it to 503 + Retry-After)."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class AdmissionTightenedError(UnavailableError):
    """Admission rejected by the degradation ladder's tighten-admission
    rung: while the SLO burn alert holds the engine at its deepest
    degradation level, submits BELOW the configured priority floor are
    shed at the door so the capacity they would take keeps the
    high-priority promises alive.  Typed and RETRYABLE — the ladder
    steps back up when the alert clears, and the request will admit
    then (the HTTP front end maps this to 503 + Retry-After)."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class _Callers:
    """Who stands before the engine lock.  The step loop gives the lock
    up after a tick and takes it again at once, before a caller it woke
    can: ``submit`` then stands there for seconds and a full engine's
    queue fills by luck.  So callers are counted, and between two ticks
    the loop waits, for at most ``turn_s``, until those that stood there
    have had the lock."""

    def __init__(self):
        self._cv = threading.Condition()
        self._n = 0

    def arrive(self) -> None:
        with self._cv:
            self._n += 1

    def admitted(self) -> None:
        with self._cv:
            self._n -= 1
            if not self._n:
                self._cv.notify_all()

    def let_in(self, turn_s: float) -> None:
        if self._n:
            with self._cv:
                self._cv.wait_for(lambda: not self._n, turn_s)


class _Record:
    """Engine-side per-request state (the pool keeps only slot state).
    ``prompt`` is retained host-side because it IS the recovery story:
    prompt + ``tokens`` (the committed output) fully determine greedy
    decode state, so a failed step resubmits their concatenation."""

    __slots__ = ("rid", "stream", "state", "prompt", "prompt_len",
                 "max_new", "deadline_abs", "submit_t", "first_t",
                 "last_t", "tokens", "retries", "priority", "tenant",
                 "preempts", "preempted_at", "sampling", "adapter",
                 "lock_wait_s", "admit_t", "commit_steps")

    def __init__(self, rid, stream, prompt, max_new, deadline_abs,
                 submit_t, priority=0, tenant=None, sampling=None,
                 adapter=0, lock_wait_s=None):
        self.rid = rid
        self.stream = stream
        self.state = RequestState.QUEUED
        self.prompt = prompt
        self.prompt_len = int(prompt.shape[0])
        self.max_new = max_new
        self.deadline_abs = deadline_abs
        self.submit_t = submit_t
        self.first_t = None
        self.last_t = None
        self.tokens = []
        self.retries = 0
        self.priority = priority
        self.tenant = tenant
        self.preempts = 0
        self.preempted_at = None
        # resolved per-request sampling config (None = greedy under the
        # pool defaults) and LoRA adapter id — they ride the record so
        # EVERY resubmit path (recovery, restore, migration) reproduces
        # the request's own stream and adapter, never a pool global
        self.sampling = sampling
        self.adapter = adapter
        # the request's own timeline, tracer or not: how long submit()
        # stood before the engine lock (perf_counter seconds; None for
        # a request that came in another way: deferred, replayed,
        # adopted) and the engine-clock instant it first took a slot.
        # ``submit_t`` is stamped INSIDE the lock, so ttft_s/total_s
        # and the SLO plane run from admission: add ``lock_wait_s``
        self.lock_wait_s = lock_wait_s
        self.admit_t = None
        # block-diffusion pools only: per committed token, the denoising
        # step of its block that committed it (None on every other pool)
        self.commit_steps = None


class ServingEngine:
    """Async request scheduler with streaming, deadlines, and metrics
    over :class:`inference.GenerationPool`.

    ``model`` is a live cached-decode model (``models.TransformerLM``);
    pool knobs (``slots``, ``buckets``, ``cache_layout``,
    ``block_size``, ``num_blocks``, ``eos_id``, sampling config, ...)
    pass through ``**pool_kwargs``.  ``clock`` injects a monotonic time
    source so deadline tests are deterministic.

    ``draft_model`` switches the engine onto the speculative pool
    variant (``inference.SpeculativePool``): the scheduler is
    UNCHANGED — lifecycle, deadlines, cancellation and streaming apply
    to speculative slots verbatim (a tick just commits 1..``spec_k``+1
    tokens per slot instead of one) — and the engine gains only the
    ``serving_acceptance_rate`` gauge."""

    def __init__(self, model, max_len: int, slots: int = 4,
                 max_queue: int = 64, clock=None,
                 metrics: Optional[MetricsRegistry] = None,
                 draft_model=None, spec_k: Optional[int] = None,
                 max_retries: int = 2, slo=None, degrade: bool = False,
                 degrade_max_level: int = 3,
                 degrade_dwell_ticks: int = 2,
                 degrade_clear_ticks: int = 3,
                 degrade_admit_floor=1,
                 journal_path: Optional[str] = None,
                 journal_fsync: str = "tick", role: str = "fused",
                 **pool_kwargs):
        if int(max_queue) < 1:
            raise InvalidArgumentError(
                "max_queue must be >= 1, got %r" % (max_queue,))
        if int(max_retries) < 0:
            raise InvalidArgumentError(
                "max_retries must be >= 0 (0 = never resubmit after a "
                "step failure), got %r" % (max_retries,))
        # disaggregated serving tiers (docs §5n): "fused" is the
        # default single-engine mode (everything below is unchanged);
        # "prefill" runs admission + chunked prefill only and exports
        # completed prefills over the K/V transfer contract; "decode"
        # adopts exported transfers and goes straight to token 1
        if role not in ("fused", "prefill", "decode"):
            raise InvalidArgumentError(
                "role must be 'fused', 'prefill', or 'decode', got %r"
                % (role,))
        if role != "fused":
            if draft_model is not None:
                raise InvalidArgumentError(
                    "disaggregated tiers run the plain pool: the "
                    "speculative pool's draft state does not cross the "
                    "K/V hand-off — use role='fused' with draft_model")
            if pool_kwargs.get("spill_tier") != "disk":
                raise InvalidArgumentError(
                    "role=%r hands K/V off through the disk transfer "
                    "contract — pass spill_tier='disk' and spill_dir= "
                    "(the directory both tiers share)" % (role,))
        if role == "prefill":
            if pool_kwargs.get("prefill_chunk_tokens") is None:
                # the prefill tier's entire job is the chunk executable
                # (PR 11, reused verbatim); without it the tier would
                # run bucketed one-shot prefill and the per-role
                # compile contract would have nothing to pin
                raise InvalidArgumentError(
                    "role='prefill' needs prefill_chunk_tokens= (the "
                    "tier runs ONLY admission + chunked prefill)")
            pool_kwargs["prefill_only"] = True
        if role == "decode" \
                and pool_kwargs.get("prefill_chunk_tokens") is not None:
            # the decode tier never compiles a prefill-chunk
            # executable — that saving is part of the point (its
            # fallback re-prefill path is the bucketed session prefill)
            raise InvalidArgumentError(
                "role='decode' must not set prefill_chunk_tokens: the "
                "decode tier adopts finished prefills and never "
                "compiles the chunk executable (docs §5n)")
        self.role = str(role)
        if degrade and slo is None:
            # the ladder's control signal IS the SLO alert: without
            # objectives there is nothing to step on, and a silently
            # inert ladder would read as "degradation configured"
            raise InvalidArgumentError(
                "degrade=True needs an SLO tracker: the ladder steps on "
                "the multi-window burn alert — pass "
                "slo=serving.slo.SLOTracker([...objectives...])")
        if degrade and not 1 <= int(degrade_max_level) <= 3:
            raise InvalidArgumentError(
                "degrade_max_level must be in [1, 3] (1 preempt, "
                "2 +reduce-spec-K, 3 +tighten-admission), got %r"
                % (degrade_max_level,))
        if degrade and (int(degrade_dwell_ticks) < 1
                        or int(degrade_clear_ticks) < 1):
            raise InvalidArgumentError(
                "degrade_dwell_ticks and degrade_clear_ticks must be "
                ">= 1 tick, got %r / %r"
                % (degrade_dwell_ticks, degrade_clear_ticks))
        by_blocks = getattr(model, "generation", None) == "block_diffusion"
        if draft_model is not None:
            from ..inference.speculative import SpeculativePool

            if by_blocks:
                raise InvalidArgumentError(
                    "draft_model: generation by diffusion over blocks "
                    "does not support speculative drafts (a block step "
                    "already commits several tokens)")

            self._pool = SpeculativePool(model, draft_model, max_len,
                                         spec_k=4 if spec_k is None
                                         else spec_k, slots=slots,
                                         **pool_kwargs)
        elif spec_k is not None:
            # spec_k without a draft would silently run un-speculated;
            # the operator would only notice the missing acceptance
            # gauge on /metrics
            raise InvalidArgumentError(
                "spec_k=%r was given without draft_model: speculative "
                "decoding needs the draft — pass draft_model= (spec_k "
                "then defaults to 4), or drop spec_k for a plain "
                "engine" % (spec_k,))
        elif by_blocks:
            # the model declares how it generates: by diffusion over
            # blocks, several tokens a step (inference/block_diffusion.py);
            # the scheduler is unchanged, the pool refuses by name what
            # that kind of step cannot do
            from ..inference.block_diffusion import BlockDiffusionPool

            if role != "fused":
                raise InvalidArgumentError(
                    "role=%r: generation by diffusion over blocks has no "
                    "K/V hand-off between tiers — use role='fused'"
                    % (role,))
            self._pool = BlockDiffusionPool(model, max_len, slots=slots,
                                            **pool_kwargs)
        else:
            self._pool = GenerationPool(model, max_len, slots=slots,
                                        **pool_kwargs)
        self.max_queue = int(max_queue)
        self.max_retries = int(max_retries)
        self._clock = clock if clock is not None else time.monotonic
        # birth stamp on the ENGINE clock: health() derives uptime_s
        # from it, so /healthz says how long this engine has served
        self._started_at = self._clock()
        self._health = EngineHealth()
        # the SLO tracker (serving/slo.py) is opt-in: None — the
        # default — costs one is-None test at each observation seam,
        # keeping the tick path clean when objectives are not declared
        # (its gauges are bound onto self.metrics below)
        self._slo = slo
        # cost-attribution fingerprint: gauges refresh only when the
        # pool's executable set changes (jit.aot cost_version)
        self._cost_seen = 0
        # the allocator's version the cache gauges were last set at
        # (None: never): they are recomputed only when it moved
        self._alloc_seen = None
        # degradation ladder (docs §5j): level 0 = normal service;
        # each alert-active tick past the dwell steps DOWN one rung
        # (1 preempt low-priority, 2 +reduce spec-K, 3 +tighten
        # admission), each clear_ticks alert-free run steps back UP.
        # ticks_since_change starts "infinite" so the FIRST alerting
        # tick escalates without waiting out a dwell it never began
        self._degrade_on = bool(degrade)
        self._degrade_level = 0
        self._degrade_max = int(degrade_max_level)
        self._degrade_dwell = int(degrade_dwell_ticks)
        self._degrade_clear = int(degrade_clear_ticks)
        self._degrade_floor = _normalize_priority(degrade_admit_floor)
        self._degrade_ticks_since_change = 1 << 30
        self._degrade_clean_ticks = 0
        self._degrade_transitions = 0
        self._spec_k_full = getattr(self._pool, "spec_k", None)
        # the runtime spec-K the ladder found when it ENGAGED the
        # reduce rung (None while disengaged): restore returns to the
        # operator's setting, never blindly to the construction-time
        # ceiling — a manual set_spec_k survives a ladder excursion
        self._spec_k_saved = None
        self._live: Dict[object, _Record] = {}
        # crash-durability plane (docs §5m): the write-ahead journal —
        # admissions are durable BEFORE they can commit tokens, token
        # batches ride one `commit` record per tick, terminals close
        # them; checkpoint() compacts, restore() replays.  The writer's
        # constructor validates an existing file's fingerprint (typed
        # mismatch error naming both sides) and truncates a torn tail.
        self._journal = None if journal_path is None else JournalWriter(
            journal_path, self._pool.config_fingerprint(),
            fsync=journal_fsync)
        if self._journal is not None \
                and self._journal.max_int_rid is not None:
            # same-path restart: the adopted journal's auto int rids
            # are taken — this engine's pre-restore traffic (warm-up,
            # canaries) must not reuse them, or its own admit/terminal
            # records would stomp the crashed engine's live entries in
            # the shared file before restore() can replay them
            self._pool.advance_auto_rids(self._journal.max_int_rid + 1)
        # this tick's committed-token deltas (rid -> [tok...]) and the
        # record backlog a failed append leaves behind: the journal
        # falls BEHIND under write faults, never wrong — replay just
        # regenerates more decode work (greedy is byte-identical)
        self._jl_tick_toks: Dict[object, List[int]] = {}
        self._jl_pending: List[dict] = []
        # RESTORING state (docs §5m): /healthz answers 503+Retry-After,
        # submits are DEFERRED (parked with a live stream, admitted the
        # moment replay finishes) — never dropped
        self._restoring = False
        self._restore_retry_after_s = 1.0
        self._deferred_submits: List[tuple] = []
        # one reentrant lock serializes every pool mutation: submit and
        # cancel may race the background step loop; in pump mode it is
        # uncontended and costs nothing
        self._lock = threading.RLock()
        self._callers = _Callers()
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._timer = StepTimer()  # profiler's step-time/throughput helper
        self._tokens_total = 0
        # slot-takes and terminals ever: a traced tick reads them
        # before and after itself for its ``admitted``/``finished`` meta
        self._n_admitted = 0
        self._n_finalized = 0
        # tracing state (serving/trace.py): the last tracer a tick
        # observed (or start_trace installed) stays referenced so
        # export_chrome_trace()/post-mortem dumps work after
        # stop_trace(); the watermarks feed the drop counter and the
        # compile-event diffing — all touched only while tracing is ON
        self._tracer: Optional[trace.Tracer] = None
        self._trace_dropped_seen = 0
        self._compile_seen: Optional[dict] = None

        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._c_submitted = m.counter(
            "serving_requests_submitted_total", "requests admitted")
        self._c_done = m.counter(
            "serving_requests_completed_total", "requests finished (eos/length)")
        self._c_cancelled = m.counter(
            "serving_requests_cancelled_total", "requests cancelled by callers")
        self._c_expired = m.counter(
            "serving_requests_expired_total", "requests past their deadline")
        self._c_failed = m.counter(
            "serving_requests_failed_total", "requests failed by step errors")
        self._c_rejected = m.counter(
            "serving_admission_rejected_total",
            "submits refused with QueueFullError")
        self._c_shed = m.counter(
            "serving_requests_shed_total",
            "deadline submits shed as unattainable at admission")
        self._c_recovered = m.counter(
            "serving_requests_recovered_total",
            "requests resubmitted token-identically after a step failure")
        self._c_recoveries = m.counter(
            "serving_recoveries_total",
            "pool rebuild + resubmit recovery events")
        self._c_restarts = m.counter(
            "serving_engine_restarts_total",
            "dead background loops restarted by the supervisor")
        self._c_stalled = m.counter(
            "serving_ticks_stalled_total",
            "ticks that exceeded the supervisor's stall timeout")
        self._c_tokens = m.counter(
            "serving_tokens_emitted_total", "tokens streamed to callers")
        self._c_drawing = m.counter(
            "serving_decode_steps_drawing_total",
            "decode steps launched with a row that draws (temperature "
            "> 0): the steps whose sampler runs its sort and draw")
        self._c_gauge_refreshes = m.counter(
            "serving_cache_gauge_refreshes_total",
            "ticks on which the cache gauges were recomputed from "
            "cache_stats(): those on which the allocator's version had "
            "moved")
        # traffic-grade scheduling surface (docs §5j): preemption /
        # spill-tier / degradation accounting.  The spill gauges exist
        # only on paged pools (the spill tier is block-granular), like
        # the free-block gauge; the ladder gauge only when degrade=True
        self._c_preempts = m.counter(
            "serving_preemptions_total",
            "active requests evicted mid-decode (K/V spilled to the "
            "host-RAM tier)")
        self._c_resumes = m.counter(
            "serving_resumes_total",
            "preempted requests resumed (K/V re-mapped or paged back "
            "in from host RAM)")
        self._c_spill_bytes = m.counter(
            "serving_spill_bytes_total",
            "K/V bytes copied device-to-host at preemption (int8 "
            "caches count int8 K/V + fp32 scales)")
        self._c_tightened = m.counter(
            "serving_admission_tightened_total",
            "submits shed below the priority floor while the "
            "degradation ladder holds tighten-admission")
        self._g_preempted = m.gauge(
            "serving_preempted_requests",
            "live requests currently parked in the spill tier")
        self._g_spilled_blocks = m.gauge(
            "serving_spilled_blocks",
            "paged KV blocks in the reclaimable spilled tier "
            "(device-resident copies of preempted requests' K/V)") \
            if self._pool._layout.paged else None
        self._g_degrade = m.gauge(
            "serving_degrade_level",
            "degradation ladder level (0 normal, 1 preempt, "
            "2 +reduce-spec-K, 3 +tighten-admission)") \
            if self._degrade_on else None
        # crash-durability surface (docs §5m): journal write accounting
        # plus the restore-side reconciliation counter the acceptance
        # contract names (`serving_journal_replayed_total` must equal
        # the journal's admitted-minus-terminal record count exactly)
        self._c_journal_records = m.counter(
            "serving_journal_records_total",
            "records appended to the write-ahead request journal")
        self._c_journal_bytes = m.counter(
            "serving_journal_bytes_total",
            "framed bytes appended to the request journal")
        self._c_journal_errors = m.counter(
            "serving_journal_errors_total",
            "journal append/sync failures caught (each is retried or "
            "left pending — the journal falls behind, never lies)")
        self._c_journal_truncated = m.counter(
            "serving_journal_truncated_records_total",
            "records dropped by torn-tail truncation during replay")
        self._c_checkpoints = m.counter(
            "serving_checkpoints_total",
            "checkpoint snapshots written (journal compactions)")
        self._c_replayed = m.counter(
            "serving_journal_replayed_total",
            "live requests reconstructed from a journal by restore()")
        self._c_restores = m.counter(
            "serving_restores_total",
            "journal restore operations completed on this engine")
        self._c_trace_dropped = m.counter(
            "serving_trace_events_dropped_total",
            "flight-recorder ring overflow: trace events evicted "
            "before export (bounded tracing is observable, not silent)")
        self._g_queue = m.gauge(
            "serving_queue_depth", "requests waiting for a slot")
        self._h_queue = m.histogram(
            "serving_queue_depth_per_step", "queue depth sampled each tick",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256))
        self._g_active = m.gauge(
            "serving_active_slots", "slots currently decoding")
        self._g_occupancy = m.gauge(
            "serving_slot_occupancy", "active slots / total slots")
        self._g_kv_bytes = m.gauge(
            "serving_kv_reachable_bytes",
            "KV bytes a decode step can read right now (cache_stats)")
        self._g_kv_resident = m.gauge(
            "serving_kv_resident_bytes",
            "KV cache bytes resident on device (whole pool allocation, "
            "dtype-aware: int8 caches count int8 K/V + fp32 scales)")
        self._g_state_slot = m.gauge(
            "serving_state_bytes_per_slot",
            "bytes of recurrent state one slot holds, every entry (a "
            "constant of the model, whatever the context)") \
            if self._pool._layout.recurrent else None
        stats = self._pool.cache_stats()
        self._g_experts_read = m.gauge(
            "serving_moe_experts_read_expected",
            "held experts whose weights a decode step is expected to read "
            "at the slots live now, over the model's routed expert layers "
            "(from shapes; the series names the route the step compiled "
            "to and the experts held)",
            labels={"route": stats["moe_route"],
                    "held": str(stats["experts_held"])}) \
            if "moe_route" in stats else None
        self._g_cache_entries = {
            kind: m.gauge(
                "serving_cache_entries",
                "decode-cache entries of each layout (a model that mixes "
                "kinds has more than one series; a layer may own one of "
                "each)",
                labels={"layout": kind})
            for kind in stats["cache_entries"]}
        # a stack run several times (models.LoopedLM): the K/V planes its
        # entries hold in all, and the passes its decode steps have made
        looped = "cache_planes" in stats
        self._g_cache_planes = m.gauge(
            "serving_cache_planes",
            "K/V planes the decode-cache entries hold in all: entries x "
            "the passes of a stack run several times (one block table "
            "and one allocation a position for all of them)") \
            if looped else None
        self._c_loop_passes = m.counter(
            "serving_loop_passes_total",
            "passes through the stack made by decode steps: steps "
            "launched x the model's passes a step") if looped else None
        # window entries (jit.cache.WindowLayout): a ring of blocks a
        # slot that a context longer than the window laps
        self._c_window_lapped = m.counter(
            "serving_window_blocks_overwritten_total",
            "ring entries of the window cache entries that a slot's "
            "position has lapped (a block behind the window overwritten "
            "by a later one, or never copied from a prompt), counted once "
            "a slot from positions, whatever the number of window "
            "entries") if "window" in stats["cache_entries"] else None
        self._g_kv_free = m.gauge(
            "serving_kv_free_blocks",
            "paged allocator free blocks") \
            if self._pool._layout.paged else None
        # sharded-serving surface (docs §5k): gauges exist only when
        # the pool runs over a DecodeMesh, like the paged-only gauges.
        # The per-shard resident gauge is the satellite fix: a
        # mesh-total-only byte gauge would overstate per-chip headroom
        # by dp× exactly where the scheduler's spill decisions need
        # the per-chip number
        _mesh = getattr(self._pool, "mesh", None)
        self._g_mesh_devices = m.gauge(
            "serving_mesh_devices",
            "devices the decode mesh spans (dp * mp)") \
            if _mesh is not None else None
        self._g_kv_resident_shard = m.gauge(
            "serving_kv_resident_bytes_per_shard",
            "KV cache bytes resident in ONE dp shard's partition "
            "(mesh-total / dp; the per-chip-headroom figure along the "
            "slot/block axis)") if _mesh is not None else None
        self._g_kv_reachable_shard = m.gauge(
            "serving_kv_reachable_bytes_max_shard",
            "largest per-dp-shard reachable KV bytes right now (the "
            "most loaded shard's occupancy)") \
            if _mesh is not None else None
        # prefix-sharing / chunked-prefill surface (docs §5i): gauges
        # exist only when the feature is on, like the paged free-block
        # gauge — a dense engine's /metrics is unchanged
        self._g_prefix_hit = m.gauge(
            "serving_prefix_hit_rate",
            "admissions that matched a resident prefix / admissions "
            "(cumulative, prefix sharing)") \
            if getattr(self._pool, "prefix_sharing", False) else None
        self._g_prefix_shared = m.gauge(
            "serving_prefix_blocks_shared",
            "KV blocks currently referenced beyond their first owner "
            "(live HBM the prefix index is saving)") \
            if getattr(self._pool, "prefix_sharing", False) else None
        self._c_chunks = m.counter(
            "serving_prefill_chunks_total",
            "fixed-shape prompt chunks dispatched (chunked prefill: "
            "at most prefill_chunk_tokens of prompt work per tick)") \
            if getattr(self._pool, "prefill_chunk_tokens", None) \
            is not None else None
        self._chunks_seen = 0
        self._g_accept = m.gauge(
            "serving_acceptance_rate",
            "accepted draft tokens / drafted (speculative pool)") \
            if hasattr(self._pool, "acceptance_stats") else None
        # block-diffusion pools: slot forwards, those that carried a
        # clean block's store, and tokens committed (a step commits
        # 0..block_length tokens a sequence)
        self._c_block = None
        if hasattr(self._pool, "block_stats"):
            self._c_block = {
                "forwards_denoise": m.counter(
                    "serving_block_forwards_denoise_total",
                    "slot forwards that denoised a block"),
                "stores_carried": m.counter(
                    "serving_block_stores_carried_total",
                    "slot forwards that also stored the clean block "
                    "before theirs"),
                "tokens_committed": m.counter(
                    "serving_block_tokens_committed_total",
                    "tokens committed by denoising steps")}
        self._g_tps = m.gauge(
            "serving_tokens_per_sec",
            "tokens emitted / cumulative step time (StepTimer)")
        self._g_step = m.gauge(
            "serving_step_time_s",
            "mean wall time of pool.step(), the pool's whole turn of a "
            "tick: launch, the download's wait for the device, "
            "delivery, admissions and their prefills")
        self._h_ttft = m.histogram(
            "serving_ttft_seconds",
            "admission-to-first-token latency (lock wait excluded; see "
            "serving_submit_lock_wait_seconds)")
        self._h_itl = m.histogram(
            "serving_inter_token_seconds", "gap between consecutive tokens")
        self._h_lock_wait = m.histogram(
            "serving_submit_lock_wait_seconds",
            "submit()/cancel() entry to engine lock acquired (not part "
            "of serving_ttft_seconds, which runs from admission)")
        # cost attribution read off the compiled artifacts (jit.aot):
        # what one batched step ASKS the hardware for, per the
        # compiler's own cost/memory analyses — refreshed only when an
        # executable changes, so the steady-state tick pays an int
        # compare (docs/DESIGN.md §5h)
        self._g_step_flops = m.gauge(
            "serving_step_flops",
            "optimized-HLO FLOPs of one batched decode step/round "
            "(XLA cost_analysis of the compiled executable)")
        self._g_step_bytes = m.gauge(
            "serving_step_bytes_accessed",
            "optimized-HLO bytes accessed by one batched decode "
            "step/round (XLA cost_analysis)")
        self._g_hbm_reserved = m.gauge(
            "serving_hbm_reserved_bytes",
            "HBM the decode step's executable reserves: arguments + "
            "outputs - donated aliases + temps + generated code "
            "(XLA memory_analysis)")
        if self._slo is not None:
            self._slo.bind_metrics(m)

        # the engine IS the pool's lifecycle observer
        self._pool.on_admit = self._on_admit
        self._pool.on_tokens = self._on_tokens
        self._pool.on_finish = self._on_finish
        self._pool.on_resume = self._on_resume

        # prefill-tier hand-off plumbing (docs §5n): the pool hook
        # collects rids whose prefill completed this tick; the export
        # sweep at the tick edge writes each transfer file and fires
        # ``on_handoff(rid, info)`` — the disaggregated front's bridge
        self._export_ready: List = []
        self.on_handoff = None
        self._c_handed_off = m.counter(
            "serving_requests_handed_off_total",
            "prefill-complete requests exported over the K/V transfer "
            "contract and handed to a decode tier") \
            if role == "prefill" else None
        if role == "prefill":
            self._pool.on_prefill_done = self._on_prefill_done

        # the JournalWriter truncated a torn tail when it re-opened an
        # existing file (a crash mid-write on the SAME path — the
        # standard restart flow): surface the count now that the
        # metric/log planes exist, so the post-mortem never reads 0
        # for damage that actually happened
        if self._journal is not None and self._journal.truncated_bytes:
            self._c_journal_truncated.inc(
                self._journal.truncated_records)
            trace.instant(
                "journal.truncated",
                dropped_records=self._journal.truncated_records,
                dropped_bytes=self._journal.truncated_bytes)
            slog.emit(
                "journal.truncated", path=self._journal.path,
                dropped_records=self._journal.truncated_records,
                dropped_bytes=self._journal.truncated_bytes,
                at="open")

    # -- admission -------------------------------------------------------
    def submit(self, input_ids, max_new_tokens: int, request_id=None,
               deadline_s: Optional[float] = None, priority=0,
               tenant=None, temperature=None, top_k=None, top_p=None,
               seed=None, adapter: int = 0) -> ResponseStream:
        """Admit one request; returns its :class:`ResponseStream`.

        ``priority`` (an int, or a named class from
        ``PRIORITY_CLASSES``: higher admits first, preempts last, and
        survives admission tightening) and ``tenant`` (a hashable
        fairness-cap key when the pool was built with
        ``tenant_slot_cap=``) are scheduling metadata passed through to
        the pool's candidate selection (docs/DESIGN.md §5j).

        ``temperature``/``top_k``/``top_p``/``seed`` are THIS request's
        sampling config (docs §5q: sampling is per-request data, not
        engine config; None fields take the pool's constructor
        defaults) and ``adapter`` its LoRA adapter id (0 = base model).
        The config is resolved ONCE here — seed included — and rides
        the request record, so recovery, journal replay and migration
        all continue the same sampled stream byte-identically.

        Fails fast: :class:`QueueFullError` past ``max_queue`` waiting
        requests (retryable), :class:`DeadlineUnattainableError` when
        the observed tick rate says ``deadline_s`` cannot be met
        (retryable, with a ``retry_after_s`` hint),
        :class:`AdmissionTightenedError` for below-floor priorities
        while the degradation ladder holds its deepest rung
        (retryable), the pool's typed errors for invalid
        prompts/budgets/duplicate ids, ``PreconditionNotMetError`` once
        draining.  ``deadline_s`` is a wall-clock budget from NOW —
        queued or decoding, the request is expired (slot and blocks
        freed) at the first tick past it."""
        priority = _normalize_priority(priority)
        if deadline_s is not None and not (float(deadline_s) > 0):
            # `not (x > 0)` instead of `x <= 0`: NaN fails both
            # comparisons, and a NaN deadline would otherwise admit a
            # request that can never expire
            raise InvalidArgumentError(
                "deadline_s must be > 0 (or None for no deadline), "
                "got %r" % (deadline_s,))
        # the wait for the engine lock is the request's, tracer or not:
        # one clock read before the lock, one inside it.  With a tracer
        # the same wait is a ``submit.lock_wait`` span on the CALLING
        # thread, closed by hand at the first line under the lock so
        # that the body stays textually inside ``with self._lock``
        t_enter = time.perf_counter()
        tr = trace.active()
        waiting = None
        if tr is not None:
            waiting = tr.span("submit.lock_wait", rid=request_id)
            waiting.__enter__()
        self._callers.arrive()
        with self._lock:
            self._callers.admitted()
            lock_wait = time.perf_counter() - t_enter
            if waiting is not None:
                waiting.__exit__(None, None, None)
            self._h_lock_wait.observe(lock_wait)
            if self._draining:
                raise PreconditionNotMetError(
                    "engine is draining/shut down: admissions are "
                    "stopped (drain()/shutdown() was called)")
            # resolve the per-request sampling config and adapter id at
            # the admission edge (typed errors for bad values belong to
            # the submit call, not a later tick) — the resolved seed is
            # what makes every downstream resubmit deterministic
            samp = self._pool._resolve_sampling(temperature, top_k,
                                                top_p, seed)
            adapter = self._pool._check_adapter(adapter)
            if self._restoring:
                # RESTORING defers admission, never drops it: the
                # journal replay owns the pool right now, so the
                # request is parked with a LIVE stream and admitted
                # through the normal path the moment replay finishes
                # (_end_restore).  An auto request's id is assigned AT
                # that admission, not now — a provisional id handed
                # out here could collide with a journaled request's
                # identity (both engines allocate auto ints from 0),
                # so ``stream.request_id`` is None until the engine
                # leaves RESTORING, which is honest rather than a
                # value that might have to change.  /healthz says
                # 503 + Retry-After meanwhile, so well-behaved HTTP
                # callers back off instead of parking.
                if len(self._deferred_submits) >= self.max_queue:
                    # the deferral parks requests in engine memory:
                    # the SAME backpressure bound as the wait queue
                    # applies, or a caller ignoring the 503 could park
                    # unbounded prompts during a long replay
                    self._c_rejected.inc()
                    raise QueueFullError(
                        "restore in progress and the deferred-submit "
                        "queue is full (%d waiting >= max_queue=%d); "
                        "back off and retry after the restore"
                        % (len(self._deferred_submits), self.max_queue))
                if request_id is not None and (
                        request_id in self._live or any(
                            e[0] == request_id
                            for e in self._deferred_submits)):
                    # detectable NOW, so the caller gets the same
                    # typed 409-mapped error the normal path raises —
                    # a 200 + FAILED stream would make an idempotency-
                    # keyed retry look like a hard generation failure.
                    # (A collision with a not-yet-replayed journaled
                    # rid cannot be known here; that one does surface
                    # on the stream.)
                    raise DuplicateRequestError(
                        "request_id %r is already live or deferred on "
                        "this restoring engine" % (request_id,))
                ids = np.asarray(getattr(input_ids, "value", input_ids))
                if self._journal is not None:
                    self._check_journal_rid(request_id)
                stream = ResponseStream(self, request_id,
                                        int(max_new_tokens))
                # the deadline anchors at SUBMIT time ("a wall-clock
                # budget from NOW" is the documented contract): the
                # restore wait counts against it, so a request whose
                # budget the replay consumed expires honestly instead
                # of being served long past its SLA
                self._deferred_submits.append(
                    (request_id, ids.astype(np.int32),
                     int(max_new_tokens),
                     (None if deadline_s is None
                      else self._clock() + float(deadline_s)),
                     priority, tenant, samp, adapter, stream))
                trace.instant("req.deferred", rid=request_id,
                              restoring=True)
                return stream
            if self._degrade_level >= 3 and priority < self._degrade_floor:
                # tighten-admission rung: below-floor traffic is shed at
                # the door while both burn windows say the engine cannot
                # keep its promises at current load — the ladder's last
                # defensive move before the only option is queue growth
                self._c_tightened.inc()
                trace.instant("req.shed", rid=request_id,
                              priority=priority, tightened=True)
                slog.emit("req.shed", rid=request_id, priority=priority,
                          tightened=True,
                          degrade_level=self._degrade_level)
                raise AdmissionTightenedError(
                    "admission tightened: the degradation ladder is at "
                    "level %d (SLO burn alert active) and priority %d "
                    "is below the floor %d; retry when the alert "
                    "clears, or submit at/above the floor"
                    % (self._degrade_level, priority,
                       self._degrade_floor))
            depth = self._pool.queue_depth
            if depth >= self.max_queue:
                self._c_rejected.inc()
                raise QueueFullError(
                    "serving queue is full (%d waiting >= max_queue=%d); "
                    "back off and retry, or raise max_queue/slots"
                    % (depth, self.max_queue))
            ids = np.asarray(getattr(input_ids, "value", input_ids))
            if deadline_s is not None:
                est = self._deadline_estimate_s(
                    int(max_new_tokens),
                    int(ids.shape[0]) if ids.ndim else 0)
                if est is not None and est > float(deadline_s):
                    self._c_shed.inc()
                    trace.instant("shed", rid=request_id,
                                  deadline_s=float(deadline_s),
                                  estimate_s=est)
                    slog.emit("req.shed", rid=request_id,
                              deadline_s=float(deadline_s),
                              estimate_s=round(est, 6))
                    raise DeadlineUnattainableError(
                        "deadline_s=%.3g cannot be met: the live "
                        "backlog and observed tick rate put completion "
                        "~%.3gs out; shed at admission (retryable) — "
                        "retry after ~%.3gs, or relax the deadline"
                        % (float(deadline_s), est,
                           max(0.001, est - float(deadline_s))),
                        retry_after_s=max(0.001, est - float(deadline_s)))
            now = self._clock()
            deadline_abs = None if deadline_s is None \
                else now + float(deadline_s)
            if self._journal is not None:
                self._check_journal_rid(request_id)
            rid = self._pool.submit(ids, max_new_tokens,
                                    request_id=request_id,
                                    priority=priority, tenant=tenant,
                                    deadline=deadline_abs,
                                    adapter=adapter, _sampling=samp)
            stream = ResponseStream(self, rid, int(max_new_tokens))
            self._live[rid] = _Record(
                rid, stream, ids.astype(np.int32), int(max_new_tokens),
                deadline_abs, now, priority=priority, tenant=tenant,
                sampling=samp, adapter=adapter, lock_wait_s=lock_wait)
            if self._journal is not None:
                # WAL discipline: the admission is durable BEFORE the
                # request can commit a token.  A failed (retried)
                # append REJECTS the admission with the typed retryable
                # error — strictly better than serving a request the
                # journal could never replay.
                try:
                    self._journal_admit(rid, ids, max_new_tokens,
                                        deadline_s, priority, tenant,
                                        sampling=samp, adapter=adapter)
                except Exception as e:  # noqa: BLE001 - reject, typed
                    self._pool.cancel(rid)
                    self._live.pop(rid, None)
                    raise JournalWriteError(
                        "admission rejected: the request journal could "
                        "not record it (%s: %s); retry — an admission "
                        "the journal cannot replay would be silently "
                        "non-durable" % (type(e).__name__,
                                         str(e)[:200])) from e
            self._c_submitted.inc()
            trace.instant("req.queued", rid=rid,
                          prompt_tokens=int(ids.shape[0]),
                          max_new_tokens=int(max_new_tokens),
                          deadline_s=deadline_s,
                          priority=priority or None, tenant=tenant,
                          lock_wait_s=lock_wait)
            # the req.admitted log line is emitted at POOL admission
            # (_on_admit, when the request takes a slot): only there is
            # the prefix-hit outcome known, and the line must carry it
            self._g_queue.set(self._pool.queue_depth)
        self._wake.set()
        return stream

    # -- pool hooks (fire inside pool.step, under the engine lock) -------
    def _on_admit(self, rid, slot, prompt_len):
        rec = self._live.get(rid)
        if rec is not None:
            self._n_admitted += 1
            rec.state = RequestState.PREFILLING
            if rec.admit_t is None:
                # the FIRST time the request takes a slot (a recovery's
                # re-admission does not move it): queue_wait_s on the
                # terminal record = this - submit_t
                rec.admit_t = self._clock()
            # matched prefix tokens of THIS admission (the pool stamps
            # it right before firing the hook; None = sharing off, and
            # the logger drops None fields)
            hit = getattr(self._pool, "last_admit_prefix_tokens", None)
            trace.instant("req.prefilling", rid=rid, slot=slot,
                          prompt_tokens=prompt_len,
                          prefix_hit_tokens=hit)
            slog.emit("req.admitted", rid=rid, slot=slot,
                      prompt_tokens=prompt_len,
                      max_new_tokens=rec.max_new,
                      deadline_s=(None if rec.deadline_abs is None
                                  else round(rec.deadline_abs
                                             - rec.submit_t, 6)),
                      queue_depth=self._pool.queue_depth,
                      prefix_hit_tokens=hit)

    def _on_tokens(self, batch) -> None:
        """The tokens one download delivered, ``(rid, token,
        commit_step)`` in the order the pool committed them, in ONE call
        (docs §5t): one clock read stands for all of them, the
        histograms, the SLO objectives and the token counter move once
        for the batch.  What stays a token's own: the stream's put and
        the record's append, in that order."""
        now = self._clock()
        live, journal = self._live, self._journal is not None
        ttfts, gaps = [], []
        try:
            for rid, tok, step in batch:
                rec = live.get(rid)
                if rec is None:  # pool used standalone alongside the engine
                    continue
                # deliver BEFORE committing: if stream delivery faults
                # (the `stream.deliver` injection seam, or a real
                # consumer-side error surfacing through the queue), the
                # token is not yet in rec.tokens, so recovery re-prefills
                # WITHOUT it and greedy decode regenerates exactly this
                # token — delivered-once and committed stay equal, never
                # one ahead of the other.  A fault at token k of a batch
                # leaves the tokens before k delivered and committed and
                # k onward neither
                rec.stream._put_token(tok)
                rec.tokens.append(tok)
                if rec.first_t is None:
                    rec.first_t = now
                    rec.state = RequestState.DECODING
                    trace.instant("req.decoding", rid=rid,
                                  ttft_s=now - rec.submit_t)
                    ttfts.append(now - rec.submit_t)
                else:
                    gaps.append(now - rec.last_t)
                rec.last_t = now
                if step is not None:
                    if rec.commit_steps is None:
                        rec.commit_steps = []
                    rec.commit_steps.append(step)
                if journal:
                    # buffered, not written: the tick's deltas ride ONE
                    # commit record at flush (journal bandwidth stays
                    # O(ticks), not O(tokens)), and a lost tail only
                    # re-decodes at restore
                    self._jl_tick_toks.setdefault(rid, []).append(tok)
        finally:
            # what was delivered is observed, a faulted batch's too
            n = len(ttfts) + len(gaps)
            self._h_ttft.observe_many(ttfts)
            self._h_itl.observe_many(gaps)
            if self._slo is not None:
                self._slo.observe_latencies("ttft", ttfts)
                self._slo.observe_latencies("inter_token", gaps)
            self._c_tokens.inc(n)
            self._tokens_total += n

    def _on_finish(self, rid, tokens, reason):
        rec = self._live.pop(rid, None)
        if rec is None:
            return
        self._pool.collect(rid)  # frees the rid; tokens already streamed
        self._c_done.inc()
        # finalize from the ENGINE's record, not the pool's `tokens`:
        # after a recovery the pool only saw the post-resubmit tail,
        # while rec.tokens carries the request's full committed output
        # (identical to `tokens` when no recovery happened)
        self._finalize(rec, RequestState.DONE, reason, rec.tokens)

    def _on_resume(self, rid, info):
        """Pool hook: a preempted request's K/V were restored and its
        slot re-activated (fires inside ``pool.step``'s refill, under
        the engine lock).  The decision is logged at the moment it
        happened, joined to the current trace tick."""
        rec = self._live.get(rid)
        if rec is None:
            return
        rec.state = RequestState.DECODING
        self._c_resumes.inc()
        now = self._clock()
        wait_s = None if rec.preempted_at is None \
            else round(now - rec.preempted_at, 6)
        rec.preempted_at = None
        # restart the inter-token clock at the RESUME moment: the
        # parked wait is scheduler time, not decode cadence — without
        # this, the first post-resume token would observe the whole
        # park as one inter_token latency, and a ladder that preempts
        # would feed its own SLO alert the violation that keeps it
        # preempting (self-sustaining degradation)
        if rec.last_t is not None:
            rec.last_t = now
        trace.instant("sched.resume", rid=rid, slot=info.get("slot"),
                      blocks_remapped=info.get("blocks_remapped"),
                      blocks_uploaded=info.get("blocks_uploaded"),
                      wait_s=wait_s)
        slog.emit("sched.resume", rid=rid, slot=info.get("slot"),
                  blocks_remapped=info.get("blocks_remapped"),
                  blocks_uploaded=info.get("blocks_uploaded"),
                  committed_tokens=info.get("committed_tokens"),
                  wait_s=wait_s)

    # -- disaggregated hand-off (docs §5n) -------------------------------
    def _on_prefill_done(self, rid) -> None:
        """Pool hook (prefill role only): ``rid``'s prompt is fully
        resident and its first token committed — queue it for the
        export sweep at this tick's edge.  The sweep, not the hook,
        does the device gather + file write: the hook fires inside
        ``pool.step`` and must stay cheap."""
        self._export_ready.append(rid)

    def _export_sweep(self) -> None:
        """Export every prefill-complete request queued this tick:
        gather + write its transfer file (the ``xfer.write`` seam),
        fire ``on_handoff(rid, info)`` with everything the decode tier
        needs — BEFORE the tier-terminal ``HANDED_OFF`` finalize, so
        the front's hand-off record exists before the stream closes —
        and finalize the tier's involvement.  A failed export degrades,
        never loses: the parked K/V is cancelled and the hand-off
        carries ``path=None`` — the decode tier falls back to
        prompt+committed resubmit, byte-identical under greedy decoding
        (the O(1)-cache contract)."""
        if not self._export_ready:
            return
        ready, self._export_ready = self._export_ready, []
        for rid in ready:
            if not self._pool.has_prefill_done(rid):
                continue  # cancelled / expired / recovered away
            rec = self._live.get(rid)
            if rec is None:
                # engine-side record gone (raced a cancel): drop the
                # parked pool state too, nothing to hand off
                try:
                    self._pool.cancel(rid)
                except NotFoundError:
                    pass
                continue
            error = None
            try:
                info = self._pool.export_kv(rid)
            except BaseException as e:  # noqa: BLE001 - degrade, not lose
                error = "%s: %s" % (type(e).__name__, str(e)[:200])
                try:
                    self._pool.cancel(rid)
                except NotFoundError:
                    pass
                info = {"rid": rid, "path": None, "transfer_bytes": 0,
                        "blocks_written": 0,
                        "committed_tokens": len(rec.tokens)}
            self._live.pop(rid, None)
            info = dict(info)
            info.update(
                prompt=rec.prompt, tokens=list(rec.tokens),
                prompt_len=rec.prompt_len, max_new_tokens=rec.max_new,
                priority=rec.priority, tenant=rec.tenant,
                deadline_abs=rec.deadline_abs, submit_t=rec.submit_t,
                exported_at=self._clock(), error=error)
            if self._c_handed_off is not None:
                self._c_handed_off.inc()
            trace.instant("xfer.export", rid=rid,
                          transfer_bytes=info["transfer_bytes"],
                          blocks=info["blocks_written"],
                          committed_tokens=info["committed_tokens"],
                          degraded=error is not None or None)
            slog.emit("xfer.export", rid=rid,
                      transfer_bytes=info["transfer_bytes"],
                      blocks=info["blocks_written"],
                      committed_tokens=info["committed_tokens"],
                      error=error)
            if self.on_handoff is not None:
                self.on_handoff(rid, info)
            self._finalize(rec, RequestState.HANDED_OFF, "handoff",
                           rec.tokens)

    def adopt_transfer(self, request_id, input_ids, tokens,
                       max_new_tokens: int, priority=0, tenant=None,
                       deadline_abs=None, sampling=None,
                       adapter: int = 0) -> dict:
        """Decode-role admission: adopt one handed-off request —
        ``input_ids`` + committed ``tokens`` are the journal-grade
        ground truth, the transfer file (if present and exact) is the
        K/V fast path.  The request re-parks straight into the spill
        tier via ``adopt_spill`` and resumes into DECODING at the next
        refill with NO re-prefill; any adoption miss (stale/alien/
        missing file) falls back to prompt+committed resubmit —
        byte-identical either way.  Committed tokens are NOT replayed
        into the returned stream: the front already delivered them
        live off the prefill tier's stream.

        Returns ``{"stream": ResponseStream, "adopted_from_file":
        bool}``.  No queue-depth gate: admission control ran at the
        prefill tier's door, and refusing a mid-flight hand-off here
        would drop a request both tiers already invested in."""
        if self.role != "decode":
            raise PreconditionNotMetError(
                "adopt_transfer is the decode tier's admission "
                "path (this engine's role is %r)" % (self.role,))
        return self._adopt_live(request_id, input_ids, tokens,
                                max_new_tokens, priority, tenant,
                                deadline_abs, sampling, adapter)

    def adopt_migration(self, request_id, input_ids, tokens,
                        max_new_tokens: int, priority=0, tenant=None,
                        deadline_abs=None, sampling=None,
                        adapter: int = 0) -> dict:
        """Fleet live-migration admission (docs/DESIGN.md §5o): the
        same adoption mechanics as :meth:`adopt_transfer` — transfer
        file as the K/V fast path, prompt+committed resubmit as the
        byte-identical fallback — but for FUSED engines behind a
        :class:`~paddle_tpu.serving.fleet.ServingFleet`, which migrate
        live requests among peers rather than across tier roles.  A
        prefill-role engine cannot adopt (it has no decode executable
        to finish the request with)."""
        if self.role == "prefill":
            raise PreconditionNotMetError(
                "a prefill-role engine cannot adopt a migrated "
                "request: it has no decode step to finish it with")
        return self._adopt_live(request_id, input_ids, tokens,
                                max_new_tokens, priority, tenant,
                                deadline_abs, sampling, adapter)

    def _adopt_live(self, request_id, input_ids, tokens,
                    max_new_tokens: int, priority=0, tenant=None,
                    deadline_abs=None, sampling=None,
                    adapter: int = 0) -> dict:
        """Shared adoption body behind :meth:`adopt_transfer` (tier
        hand-off) and :meth:`adopt_migration` (fleet migration): the
        role gates differ, the mechanics — journal WAL, ``adopt_spill``
        fast path, resubmit fallback — must not.  ``sampling`` is the
        donor's wire 5-list (or an already-parsed config);
        ``adapter`` must name a loaded bank row HERE — the typed
        rejection fires before any state lands, so the fleet router can
        hot-load the adapter and retry the adoption."""
        with self._lock:
            if self._draining:
                raise PreconditionNotMetError(
                    "engine is draining/shut down: hand-offs are "
                    "stopped")
            if request_id in self._live:
                raise DuplicateRequestError(
                    "request_id %r is already live on this engine"
                    % (request_id,))
            priority = _normalize_priority(priority)
            if isinstance(sampling, (list, tuple)) \
                    and not isinstance(sampling, _SamplingConfig):
                sampling = _samp_from_json(sampling)
            adapter = self._pool._check_adapter(adapter)
            ids = np.asarray(getattr(input_ids, "value",
                                     input_ids)).astype(np.int32)
            toks = [int(t) for t in tokens]
            now = self._clock()
            stream = ResponseStream(self, request_id,
                                    int(max_new_tokens))
            rec = _Record(request_id, stream, ids,
                          int(max_new_tokens), deadline_abs, now,
                          priority=priority, tenant=tenant,
                          sampling=sampling, adapter=adapter)
            rec.tokens = list(toks)
            if toks:
                # the decode tier observes ITL only from here on: TTFT
                # belongs to the prefill tier (and end-to-end to the
                # front) — the first post-adopt token must not book
                # the whole prefill+hand-off as one inter-token gap
                rec.first_t = rec.last_t = now
            if self._journal is not None:
                # WAL discipline survives disaggregation: the adoption
                # is durable (admit + the committed history as one
                # commit record) BEFORE the request can decode, so a
                # decode-tier crash mid-adopt replays prompt+committed
                # — the transfer file, if still exact, is re-adopted
                # at restore
                self._check_journal_rid(request_id)
                try:
                    self._journal_admit(
                        request_id, ids, max_new_tokens,
                        (None if deadline_abs is None
                         else max(0.001, deadline_abs - now)),
                        priority, tenant, sampling=sampling,
                        adapter=adapter)
                    if toks:
                        self._jl_tick_toks.setdefault(
                            request_id, []).extend(toks)
                        self._journal_flush()
                except Exception as e:  # noqa: BLE001 - reject, typed
                    raise JournalWriteError(
                        "hand-off rejected: the request journal could "
                        "not record the adoption (%s: %s); retry"
                        % (type(e).__name__, str(e)[:200])) from e
            adopted = self._pool.adopt_spill(
                request_id, ids, toks, int(max_new_tokens),
                priority=priority, tenant=tenant,
                deadline=deadline_abs)
            if adopted:
                rec.state = RequestState.PREEMPTED
                rec.preempted_at = now
            else:
                self._resubmit_record(rec)
            self._live[request_id] = rec
            self._c_submitted.inc()
            trace.instant("xfer.adopt", rid=request_id,
                          from_file=adopted,
                          committed_tokens=len(toks))
            slog.emit("xfer.adopt", rid=request_id,
                      adopted_from_file=adopted,
                      committed_tokens=len(toks),
                      prompt_tokens=int(ids.shape[0]))
        self._wake.set()
        return {"stream": stream, "adopted_from_file": bool(adopted)}

    def migrate_out(self, request_id) -> dict:
        """Surrender one live request for adoption by a peer engine —
        the donor half of fleet live migration (docs/DESIGN.md §5o).

        A DECODING victim on the disk spill tier is preempted first
        (its written K/V lands in a transfer file under the shared
        spill naming) and then DETACHED — the file survives, the pool
        forgets the request — so the adopting peer resumes it through
        ``adopt_spill`` with zero re-prefill.  Anything else (queued,
        mid-prefill, host-tier parked, preempt-refused) is simply
        cancelled pool-side: the returned prompt+committed entry is the
        journal-grade ground truth and the peer's resubmit path
        regenerates byte-identically under greedy decoding.

        The engine finalizes its side ``HANDED_OFF``/"migrated" (the
        journal stops tracking the rid, the local stream terminates
        with the tier-terminal the fleet front never surfaces) and
        returns the migration entry: ``{"rid", "prompt", "tokens",
        "max_new", "priority", "tenant", "deadline_abs", "retries",
        "sampling", "adapter", "spill_path"}`` — everything
        ``adopt_migration`` needs (the sampling 5-list and adapter id
        let the peer continue the request's own stream under its own
        adapter, docs §5q)."""
        with self._lock:
            # the entry carries the committed tokens: level with the
            # device first (the request may end in the step in flight)
            self._settle_pool()
            rec = self._live.get(request_id)
            if rec is None:
                raise NotFoundError(
                    "request_id %r is not live on this engine"
                    % (request_id,))
            pool = self._pool
            spill_path = None
            if rec.state == RequestState.DECODING \
                    and pool.spill_tier == "disk" \
                    and pool.can_preempt(rec.rid):
                try:
                    self._do_preempt(rec, "migrate")
                except Exception:  # noqa: BLE001 - degrade to resubmit
                    pass
            if rec.state == RequestState.PREEMPTED:
                try:
                    spill_path = pool.detach_spilled(rec.rid)["path"]
                except (NotFoundError, PreconditionNotMetError):
                    # host-tier parked (no file to hand over) or raced
                    # away: the prompt+committed entry still carries
                    # the full resume state
                    pool.cancel(rec.rid)
            else:
                pool.cancel(rec.rid)
            self._live.pop(request_id, None)
            entry = {"rid": rec.rid,
                     "prompt": rec.prompt,
                     "tokens": list(rec.tokens),
                     "max_new": rec.max_new,
                     "priority": rec.priority,
                     "tenant": rec.tenant,
                     "deadline_abs": rec.deadline_abs,
                     "retries": rec.retries,
                     "sampling": _samp_json(rec.sampling),
                     "adapter": int(rec.adapter),
                     "spill_path": spill_path}
            trace.instant("sched.migrate_out", rid=rec.rid,
                          spilled=spill_path is not None,
                          committed_tokens=len(rec.tokens))
            slog.emit("sched.migrate_out", rid=rec.rid,
                      spilled=spill_path is not None,
                      committed_tokens=len(rec.tokens),
                      remaining=rec.max_new - len(rec.tokens))
            self._finalize(rec, RequestState.HANDED_OFF, "migrated",
                           rec.tokens)
            self._journal_flush()
            return entry

    # -- preemption + the degradation ladder (docs §5j) ------------------
    def preempt(self, request_id=None, reason: str = "manual"):
        """Evict one actively-decoding request into the host-RAM spill
        tier; it resumes automatically (byte-identically) when the
        scheduler next has capacity for it.

        With ``request_id=None`` the engine auto-selects the victim —
        the LOWEST-priority decoding request, youngest first (the least
        important, least-invested work parks) — and returns its id, or
        None when nothing is preemptable (no decoding request passes
        ``pool.can_preempt``).  With an explicit id, typed errors
        propagate: ``NotFoundError`` for unknown/non-decoding requests,
        the pool's preconditions otherwise."""
        with self._lock:
            # a victim is chosen among what is live once the step in
            # flight has been delivered (its hooks change ``_live``)
            self._settle_pool()
            if request_id is None:
                victims = [r for r in self._live.values()
                           if r.state == RequestState.DECODING
                           and self._pool.can_preempt(r.rid)]
                if not victims:
                    return None
                rec = min(victims,
                          key=lambda r: (r.priority, -r.submit_t))
            else:
                rec = self._live.get(request_id)
                if rec is None:
                    raise NotFoundError(
                        "request_id %r is not live on this engine"
                        % (request_id,))
            return self._do_preempt(rec, reason)

    def _do_preempt(self, rec: _Record, reason: str):
        """Preempt ``rec`` (caller holds the lock): spill via the pool,
        flip the record to PREEMPTED, and make the decision auditable —
        one flight-recorder event and one structured-log line, both
        carrying the tick join key."""
        info = self._pool.preempt(rec.rid)
        rec.state = RequestState.PREEMPTED
        rec.preempts += 1
        rec.preempted_at = self._clock()
        self._c_preempts.inc()
        self._c_spill_bytes.inc(info["spill_bytes"])
        trace.instant("sched.preempt", rid=rec.rid, reason=reason,
                      priority=rec.priority,
                      committed_tokens=info["committed_tokens"],
                      blocks_spilled=info["blocks_spilled"],
                      spill_bytes=info["spill_bytes"])
        slog.emit("sched.preempt", rid=rec.rid, reason=reason,
                  priority=rec.priority, tenant=rec.tenant,
                  committed_tokens=info["committed_tokens"],
                  blocks_spilled=info["blocks_spilled"],
                  blocks_freed=info["blocks_freed"],
                  spill_bytes=info["spill_bytes"],
                  degrade_level=self._degrade_level or None)
        return rec.rid

    def _degrade_eval(self) -> None:
        """One ladder evaluation per tick (caller holds the lock; runs
        BEFORE the pool step so a preemption frees capacity the same
        tick's refill can hand to waiting high-priority work).

        Step DOWN one level per alerting tick once ``dwell`` ticks have
        passed since the last change; step back UP one level after
        ``clear`` consecutive alert-free ticks.  Rungs are cumulative:
        1 preempt-for-priority, 2 +reduce spec-K to 1 (speculative
        pools), 3 +tighten admission below the priority floor.  Every
        transition emits ``sched.degrade``/``sched.restore`` to the
        flight recorder and the structured log."""
        if not self._degrade_on:
            return
        alerting = self._slo.alerting_names()
        self._degrade_ticks_since_change += 1
        if alerting:
            self._degrade_clean_ticks = 0
            if self._degrade_level < self._degrade_max and \
                    self._degrade_ticks_since_change >= self._degrade_dwell:
                self._set_degrade_level(self._degrade_level + 1, alerting)
        else:
            self._degrade_clean_ticks += 1
            if self._degrade_level > 0 and \
                    self._degrade_clean_ticks >= self._degrade_clear:
                self._set_degrade_level(self._degrade_level - 1, alerting)
                self._degrade_clean_ticks = 0
        if self._degrade_level >= 1:
            self._preempt_for_priority()

    def _set_degrade_level(self, level: int, alerting) -> None:
        prev, self._degrade_level = self._degrade_level, level
        self._degrade_ticks_since_change = 0
        self._degrade_transitions += 1
        actions = []
        if level >= 1:
            actions.append("preempt-low-priority")
        spec = getattr(self._pool, "set_spec_k", None)
        if spec is not None and self._spec_k_full is not None \
                and self._spec_k_full > 1:
            if level >= 2 and prev < 2:
                # engage the rung: remember the OPERATOR's runtime
                # setting (which may itself be a manual set_spec_k
                # tune) and drop to 1 — restore must return there, not
                # to the construction-time ceiling
                self._spec_k_saved = self._pool.spec_k_active
                if self._spec_k_saved != 1:
                    spec(1)
                    actions.append("spec_k->1")
            elif level < 2 and prev >= 2 \
                    and self._spec_k_saved is not None:
                if self._pool.spec_k_active == 1 \
                        and self._spec_k_saved != 1:
                    # only undo the LADDER's own setting: an operator
                    # who re-tuned mid-degradation wins
                    spec(self._spec_k_saved)
                    actions.append("spec_k->%d" % self._spec_k_saved)
                self._spec_k_saved = None
        if level >= 3:
            actions.append("admission-floor>=%d" % self._degrade_floor)
        if self._g_degrade is not None:
            self._g_degrade.set(level)
        event = "sched.degrade" if level > prev else "sched.restore"
        trace.instant(event, level=level, prev=prev,
                      alerting=list(alerting) or None)
        slog.emit(event, level=level, prev=prev,
                  alerting=list(alerting) or None,
                  actions=actions or None)

    def _preempt_for_priority(self) -> None:
        """The preempt rung: evict ONE low-priority decoding request
        per tick, and only when it actually buys something — a
        STRICTLY-higher-priority request is waiting AND the pool is out
        of slots (or its chosen candidate is block-starved).  Bounded
        and purposeful, so the ladder cannot thrash the spill tier."""
        pool = self._pool
        # only requests the refill could actually ADMIT justify a
        # victim: a tenant at its fairness cap is deferred by
        # _pick_candidate, and preempting for it would just thrash the
        # spill tier (preempt, then resume the victim into the slot
        # the capped request cannot take)
        queued = [r for r in self._live.values()
                  if r.state == RequestState.QUEUED
                  and not pool.tenant_at_cap(r.tenant)]
        if not queued:
            return
        if pool.active_count + pool.prefilling_count < pool.slots \
                and not pool.admission_blocked:
            return
        pmax = max(r.priority for r in queued)
        self._settle_pool()  # its hooks change ``_live``: not under the scan
        victims = [r for r in self._live.values()
                   if r.state == RequestState.DECODING
                   and r.priority < pmax
                   and pool.can_preempt(r.rid)]
        if not victims:
            return
        rec = min(victims, key=lambda r: (r.priority, -r.submit_t))
        self._do_preempt(rec, "degrade")

    def degradation_snapshot(self) -> dict:
        """The ladder's state — folded into ``GET /slo`` and readable
        directly; ``enabled=False`` with zeros when no ladder was
        configured."""
        out = {"enabled": self._degrade_on,
               "level": self._degrade_level,
               "max_level": self._degrade_max,
               "admit_floor": self._degrade_floor,
               "transitions": self._degrade_transitions,
               "preempted_requests": sum(
                   1 for r in self._live.values()
                   if r.state == RequestState.PREEMPTED)}
        if self._spec_k_full is not None:
            out["spec_k_active"] = self._pool.spec_k_active
            out["spec_k_full"] = self._spec_k_full
        return out

    # -- lifecycle transitions -------------------------------------------
    def _finalize(self, rec: _Record, state: str, reason: str, tokens,
                  error: Optional[str] = None) -> None:
        now = self._clock()
        toks = np.asarray(tokens if tokens is not None else rec.tokens,
                          np.int32)
        rec.state = state
        self._n_finalized += 1
        if self._journal is not None:
            # commit-before-terminal ordering: this rid's same-tick
            # token deltas must hit the journal before the record that
            # stops replay from tracking it — materialize the buffer
            # first, then queue the terminal
            self._materialize_tick_commits()
            self._jl_pending.append(
                {"t": "terminal", "rid": _jsonable_rid(rec.rid),
                 "state": state, "reason": reason})
        # every terminal path (done / cancelled / expired / failed —
        # including drain()/shutdown()'s cancels) funnels through here,
        # so an exported request timeline always closes with a terminal
        # mark, never mid-span — and the SLO tracker and structured log
        # see every terminal for the same reason
        trace.instant("req." + state.lower(), rid=rec.rid,
                      reason=reason, new_tokens=int(toks.size),
                      error=error)
        if self._slo is not None:
            self._slo.observe_terminal(state)
        slog.emit("req.terminal", rid=rec.rid, state=state,
                  finish_reason=reason, new_tokens=int(toks.size),
                  ttft_s=(None if rec.first_t is None
                          else round(rec.first_t - rec.submit_t, 6)),
                  total_s=round(now - rec.submit_t, 6),
                  retries=rec.retries or None, error=error)
        rec.stream._finalize(StreamStatus(
            request_id=rec.rid, state=state, finish_reason=reason,
            tokens=toks, prompt_tokens=rec.prompt_len,
            new_tokens=int(toks.size),
            ttft_s=(None if rec.first_t is None
                    else rec.first_t - rec.submit_t),
            total_s=now - rec.submit_t, error=error,
            lock_wait_s=rec.lock_wait_s,
            queue_wait_s=(None if rec.admit_t is None
                          else rec.admit_t - rec.submit_t),
            commit_steps=rec.commit_steps))

    def cancel(self, request_id) -> bool:
        """Abort a live request: its slot and paged blocks are freed
        mid-generation, its stream ends with state ``CANCELLED`` (the
        tokens emitted so far ride in the status record).  False if the
        id is not live (already terminal or unknown) — idempotent, so
        callers can cancel on a races-with-completion path safely."""
        t_enter = time.perf_counter()
        self._callers.arrive()
        with self._lock:
            self._callers.admitted()
            self._h_lock_wait.observe(time.perf_counter() - t_enter)
            held = self._live.get(request_id)
            if held is not None and held.state != RequestState.QUEUED:
                # it may end in the step in flight: then there is nothing
                # left to cancel, and what it streamed stands
                self._settle_pool()
            rec = self._live.pop(request_id, None)
            if rec is None:
                if request_id is not None:
                    # a submit DEFERRED during RESTORING is cancellable
                    # too (the HTTP disconnect-reclaim path must not
                    # leave an orphan to decode its whole budget for
                    # nobody after the restore); auto-rid deferrals
                    # have no id yet and cannot be addressed — bounded
                    # by the deferral's max_queue cap
                    for i, entry in enumerate(self._deferred_submits):
                        if entry[0] == request_id:
                            (rid, ids, max_new, _dl, priority, tenant,
                             samp, adapter, stream) = entry
                            del self._deferred_submits[i]
                            rec = _Record(rid, stream, ids, max_new,
                                          None, self._clock(),
                                          priority=priority,
                                          tenant=tenant, sampling=samp,
                                          adapter=adapter)
                            self._c_cancelled.inc()
                            self._finalize(rec, RequestState.CANCELLED,
                                           "cancelled", [])
                            return True
                return False
            self._pool.cancel(request_id)
            self._c_cancelled.inc()
            self._finalize(rec, RequestState.CANCELLED, "cancelled",
                           rec.tokens)
            # an out-of-tick terminal must not wait for the next tick's
            # flush to become durable (there may never be one)
            self._journal_flush()
            return True

    def _expire(self) -> None:
        now = self._clock()
        late = [rid for rid, rec in self._live.items()
                if rec.deadline_abs is not None and now >= rec.deadline_abs]
        if late:
            self._settle_pool()     # one of them may end in the step in flight
        for rid in late:
            rec = self._live.pop(rid, None)
            if rec is not None:
                self._pool.cancel(rid)
                self._c_expired.inc()
                self._finalize(rec, RequestState.EXPIRED, "deadline",
                               rec.tokens)

    def settle(self) -> None:
        """Deliver whatever step the pool has in flight (it runs a step
        ahead of the host, docs §5t): after this every stream and every
        record is level with the device.  For a caller about to read
        them from outside the tick (the fleet, before it moves a
        request); the engine's own out-of-tick paths call it
        themselves."""
        with self._lock:
            self._settle_pool()

    def _settle_pool(self) -> None:
        """``pool._settle()`` under the blast radius of a step (caller
        holds the lock): its download and its hooks are the tick's, so
        a failure in them is recovered as the tick recovers one."""
        try:
            self._pool._settle()
        except Exception as e:  # noqa: BLE001 - step is the blast radius
            self._health.note_error(self._clock(), e,
                                    faults.classify_error(e))
            self._recover(e)

    def _fail_record(self, rec: _Record, exc: BaseException,
                     why: str) -> None:
        """Finalize one victim FAILED, carrying the retry count and the
        root error (the satellite contract: post-mortems read the
        stream's terminal record, not a debugger)."""
        self._c_failed.inc()
        self._finalize(
            rec, RequestState.FAILED, "error", rec.tokens,
            error=("%s (retries=%d/%d): %s"
                   % (why, rec.retries, self.max_retries,
                      str(exc)[:400]))[:500])

    def _resubmit_record(self, rec: _Record) -> None:
        """THE recovery primitive (docs §5f): resubmit one victim as
        prompt + committed tokens with its remaining budget and its
        scheduling metadata — greedy decode regenerates from there
        byte-identically (the O(1)-cache contract).  Shared by
        ``_recover`` (in-process step failure) and ``restore``
        (cross-process journal replay): both are the same operation at
        different blast radii."""
        ids = rec.prompt if not rec.tokens else np.concatenate(
            [rec.prompt, np.asarray(rec.tokens, np.int32)])
        self._pool.submit(ids, rec.max_new - len(rec.tokens),
                          request_id=rec.rid,
                          priority=rec.priority,
                          tenant=rec.tenant,
                          deadline=rec.deadline_abs,
                          adapter=rec.adapter,
                          # draws advances by the committed count, so a
                          # SAMPLED victim's re-prefill draw lands at
                          # the step its uninterrupted continuation
                          # would have used (docs §5q)
                          _sampling=self._pool._resubmit_sampling(
                              rec.sampling, len(rec.tokens)))
        rec.state = RequestState.QUEUED
        rec.preempted_at = None

    def _complete_reason(self, tokens, max_new: int) -> Optional[str]:
        """Why a request that committed ``tokens`` has nothing left to
        generate (``"eos"`` / ``"length"``), or None while it has."""
        eos = self._pool.eos_id
        if eos is not None and tokens and tokens[-1] == eos:
            return "eos"
        return "length" if len(tokens) >= max_new else None

    def _recover(self, exc: BaseException) -> None:
        """A pool step blew up mid-flight.  The batched step serves
        every live request, so none of the POOL's state can be trusted —
        but the ENGINE's host-side records can: prompt + committed
        tokens fully determine greedy decode state (the O(1)-cache
        contract), so the blast radius is REQUEST-level, not
        engine-level.  Victims whose typed classification is transient
        and whose retry budget remains are resubmitted as
        prompt+committed (greedy requests continue token-identically);
        permanent errors and exhausted budgets finalize FAILED with the
        retry count and root error.  The pool rebuild reuses every
        compiled executable — recovery costs cache re-allocation plus
        one re-prefill per survivor, never a recompile."""
        kind = faults.classify_error(exc)
        # sweep entries queued before the failure name parked pool
        # state pool.reset() is about to discard; the resubmitted
        # survivors will re-prefill and re-queue themselves
        self._export_ready = []
        survivors = []
        for rid, rec in list(self._live.items()):
            self._live.pop(rid)
            reason = self._complete_reason(rec.tokens, rec.max_new)
            if reason is not None:
                # its last token was delivered and committed in the
                # batch that faulted at a later one, before the pool
                # could finish it: nothing is left to resubmit
                self._c_done.inc()
                self._finalize(rec, RequestState.DONE, reason, rec.tokens)
                continue
            if kind == "permanent":
                self._fail_record(rec, exc, "permanent step error")
            elif rec.retries >= self.max_retries:
                self._fail_record(rec, exc, "retry budget exhausted")
            else:
                rec.retries += 1
                survivors.append(rec)
        try:
            self._pool.reset()
        except Exception as reset_exc:  # noqa: BLE001 - rebuild itself died
            for rec in survivors:
                self._fail_record(rec, reset_exc, "pool rebuild failed")
            raise
        self._c_recoveries.inc()
        trace.instant("recovery", kind=kind, error=str(exc)[:200],
                      survivors=len(survivors))
        resubmitted = 0
        for rec in survivors:  # dict order == submit order: FIFO kept
            try:
                # scheduling metadata survives recovery: a resubmitted
                # victim keeps its class/tenant/deadline — including
                # PREEMPTED victims, whose spill-tier copies died with
                # the pool (prompt+committed is the recovery source)
                self._resubmit_record(rec)
            except Exception as sub_exc:  # noqa: BLE001 - per-victim
                self._fail_record(rec, sub_exc, "resubmit failed")
                continue
            self._live[rec.rid] = rec
            self._c_recovered.inc()
            trace.instant("recovery.resubmit", rid=rec.rid,
                          retries=rec.retries,
                          committed_tokens=len(rec.tokens))
            resubmitted += 1
        self._health.note_recovery(resubmitted)
        slog.emit("engine.recovery", kind=kind,
                  survivors=len(survivors), resubmitted=resubmitted,
                  error=str(exc)[:200])

    # -- crash durability: journal, checkpoint, restore (docs §5m) -------
    def _check_journal_rid(self, request_id) -> None:
        """A journaled engine only accepts JSON-round-trippable request
        ids (int/str): anything else could not be replayed under the
        same identity, which is the whole point of recording it."""
        if request_id is None or isinstance(request_id, str):
            return
        if isinstance(request_id, (int, np.integer)) \
                and not isinstance(request_id, bool):
            return
        raise InvalidArgumentError(
            "a journaled engine needs a JSON-safe request_id (int or "
            "str, or None for auto-assignment) — got %r; the journal "
            "must replay the request under the same identity"
            % (request_id,))

    def _journal_admit(self, rid, ids, max_new, deadline_s, priority,
                       tenant, sampling=None, adapter=0) -> None:
        """Make ONE admission durable — the WAL step shared by
        ``submit()`` and ``_admit_deferred`` so the two admission
        paths can never diverge.  Drains any backlog FIRST (journal
        ORDER is replay correctness: a collected-and-reused rid would
        otherwise see the OLD request's stranded commits replayed onto
        the NEW admission), then appends + syncs the admit record.  On
        any failure a closing ghost terminal is queued — if the admit
        frame landed and only the sync failed, restore would otherwise
        resurrect a consumer-less request; a ghost terminal for an
        admit that never landed is replay-tolerated — and the error
        propagates for the caller to unwind the pool and pick its
        error channel (typed raise vs stream finalize)."""
        try:
            if self._jl_pending or self._jl_tick_toks:
                self._journal_flush()
                if self._jl_pending:
                    raise JournalWriteError(
                        "the journal has a backlog of %d unflushed "
                        "records (append failures) that must land "
                        "before a new admit record can — retry"
                        % (len(self._jl_pending),))
            self._journal_append(
                {"t": "admit", "rid": _jsonable_rid(rid),
                 "ids": [int(t) for t in ids],
                 "max_new": int(max_new),
                 "priority": int(priority), "tenant": tenant,
                 "deadline_s": (None if deadline_s is None
                                else float(deadline_s)),
                 # v2 fields (docs §5q): the request's RESOLVED
                 # sampling config and adapter id — replay resumes the
                 # same stream under the same adapter
                 "sampling": _samp_json(sampling),
                 "adapter": int(adapter),
                 # WALL clock (engine clocks may be injected and do
                 # not cross processes): restore deducts the elapsed
                 # time so a replayed deadline keeps its REMAINING
                 # budget, matching checkpoint's snapshot semantics
                 "ts": time.time()})
            self._journal.sync()
        except Exception:
            self._jl_pending.append(
                {"t": "terminal", "rid": _jsonable_rid(rid),
                 "state": RequestState.FAILED,
                 "reason": "admit-unjournaled"})
            # try to land the closing terminal NOW: if the admit frame
            # reached disk and only its fsync failed, a crash before
            # the next tick flush would otherwise resurrect a request
            # whose caller was told it was never admitted (flush is
            # non-raising — a still-broken disk just leaves it pending)
            self._journal_flush()
            raise

    def _materialize_tick_commits(self) -> None:
        """Fold this tick's buffered token deltas into ONE pending
        commit record — the single shape both call sites (_finalize's
        commit-before-terminal ordering, the tick flush) must share,
        so the record format can never diverge between them."""
        if self._jl_tick_toks:
            self._jl_pending.append(
                {"t": "commit",
                 "toks": [[_jsonable_rid(r), ts] for r, ts
                          in self._jl_tick_toks.items()]})
            self._jl_tick_toks = {}

    def _journal_append(self, rec: dict) -> int:
        """Append one record, retrying ONCE on a transient failure.
        Every caught fault emits a ``journal.error`` trace event and a
        structured-log line and bumps ``serving_journal_errors_total``,
        so the chaos harness reconciles injected ``journal.append``
        faults against the recorder exactly.  A second failure
        propagates — the caller decides (submit rejects the admission;
        the tick flush leaves the record pending and serves on)."""
        for attempt in (0, 1):
            try:
                n = self._journal.append(rec)
            except Exception as e:  # noqa: BLE001 - classify + retry
                retry = attempt == 0 \
                    and faults.classify_error(e) == "transient"
                self._c_journal_errors.inc()
                trace.instant("journal.error", record=rec.get("t"),
                              error=type(e).__name__, retried=retry)
                slog.emit("journal.error", record=rec.get("t"),
                          error=str(e)[:200], retried=retry)
                if not retry:
                    raise
                continue
            self._c_journal_records.inc()
            self._c_journal_bytes.inc(n)
            return n
        raise AssertionError("unreachable")  # pragma: no cover

    def _journal_flush(self) -> None:
        """Drain this tick's commit batch plus any backlog into the
        journal, in order, stopping (NOT raising) at a persistent
        append failure — the journal falls behind and catches up on a
        later flush; restore regenerates the gap byte-identically
        either way.  One fsync per flush under the default
        ``journal_fsync="tick"`` policy."""
        j = self._journal
        if j is None:
            return
        self._materialize_tick_commits()
        if not self._jl_pending:
            return
        while self._jl_pending:
            try:
                self._journal_append(self._jl_pending[0])
            except Exception:  # noqa: BLE001 - stays pending, serve on
                break
            self._jl_pending.pop(0)
        try:
            j.sync()
        except OSError as e:
            self._c_journal_errors.inc()
            trace.instant("journal.error", record="sync",
                          error=type(e).__name__, retried=False)
            slog.emit("journal.error", record="sync",
                      error=str(e)[:200], retried=False)

    def checkpoint(self, path: Optional[str] = None) -> dict:
        """Snapshot the live request set at a tick boundary and COMPACT
        the journal to header + one checkpoint record (tmp file +
        fsync + atomic rename).  With ``path=None`` the engine's own
        journal is compacted in place (requires ``journal_path=``);
        with an explicit ``path`` a standalone snapshot journal is
        written there — the cross-engine hand-off form — and the live
        journal is left untouched.  The engine lock IS the tick
        boundary: no step can be mid-flight while the snapshot is
        taken.  Returns ``{"path", "bytes", "records",
        "live_requests"}``."""
        with self._lock:
            if self._journal is None and path is None:
                raise PreconditionNotMetError(
                    "checkpoint() needs either a journaled engine "
                    "(journal_path= at construction) or an explicit "
                    "path to write the snapshot journal to")
            self._journal_flush()
            now = self._clock()
            live = []
            for rec in self._live.values():
                live.append({
                    "rid": _jsonable_rid(rec.rid),
                    "ids": [int(t) for t in rec.prompt],
                    "tokens": list(rec.tokens),
                    "max_new": rec.max_new,
                    "priority": rec.priority,
                    "tenant": rec.tenant,
                    # deadlines are re-armed with the REMAINING budget
                    # at restore time: absolute stamps from this
                    # engine's clock mean nothing in another process.
                    # The wall-clock stamp lets restore deduct the
                    # DOWNTIME too — an hour-long outage must not be
                    # granted back to a request whose SLA it consumed
                    "deadline_s": (None if rec.deadline_abs is None
                                   else max(0.001,
                                            rec.deadline_abs - now)),
                    "ts": time.time(),
                    "sampling": _samp_json(rec.sampling),
                    "adapter": int(rec.adapter),
                    "retries": rec.retries})
            ckpt = {"t": "checkpoint", "live": live}
            if self._journal is not None:
                info = self._journal.compact([ckpt], path=path)
                if path is None or os.path.abspath(path) \
                        == os.path.abspath(self._journal.path):
                    # the snapshot SUPERSEDES any backlog a failed
                    # flush stranded: rec.tokens above already include
                    # those commits, so appending them after the
                    # checkpoint would double-apply at replay —
                    # discard them with the history they belong to
                    self._jl_pending = []
                    self._jl_tick_toks = {}
            else:
                w = JournalWriter(path,
                                  self._pool.config_fingerprint())
                try:
                    info = w.compact([ckpt])
                finally:
                    w.close()
            self._c_checkpoints.inc()
            trace.instant("journal.checkpoint",
                          live=len(live), bytes=info["bytes"])
            slog.emit("journal.checkpoint", path=info["path"],
                      live_requests=len(live), bytes=info["bytes"])
            info["live_requests"] = len(live)
            return info

    def _begin_restore(self, retry_after_s: float = 1.0) -> None:
        """Flip the engine into RESTORING: ``health()`` reports it
        (503 + Retry-After on ``GET /healthz``) and submits are
        deferred until ``_end_restore`` (test seam: the HTTP suite
        drives the window directly)."""
        with self._lock:
            self._restoring = True
            self._restore_retry_after_s = float(retry_after_s)

    def _end_restore(self) -> None:
        """Leave RESTORING and admit every deferred submit through the
        normal path (journal admit record included) — all under ONE
        lock acquisition, so no foreign submit can interleave between
        the flag flip and the deferred admissions.  A deferred request
        whose admission now fails finalizes its stream FAILED — its
        caller already holds the stream, so the error travels there,
        not up this stack."""
        with self._lock:
            self._restoring = False
            deferred, self._deferred_submits = self._deferred_submits, []
            for args in deferred:
                self._admit_deferred(*args)
        if deferred:
            self._wake.set()

    def _admit_deferred(self, rid, ids, max_new, deadline_abs, priority,
                        tenant, samp, adapter, stream) -> None:
        """``deadline_abs`` was anchored at the original submit (the
        restore wait already counts against it — an exhausted budget
        expires at the first tick, never gets served past its SLA);
        ``samp`` was RESOLVED there too, so the request's sampling
        stream does not depend on how long the restore took or what
        replayed meanwhile."""
        with self._lock:
            now = self._clock()
            try:
                if self._draining:
                    raise PreconditionNotMetError(
                        "engine drained while the submit was deferred")
                if self._pool.queue_depth >= self.max_queue:
                    raise QueueFullError(
                        "queue filled while the submit was deferred; "
                        "back off and resubmit")
                # no deadline-estimate shed here: the estimator is cold
                # right after a restore — the deadline itself still
                # expires the request normally once admitted
                rid = self._pool.submit(ids, int(max_new),
                                        request_id=rid,
                                        priority=priority,
                                        tenant=tenant,
                                        deadline=deadline_abs,
                                        adapter=adapter,
                                        _sampling=samp)
            except Exception as e:  # noqa: BLE001 - to the stream
                rec = _Record(rid, stream, ids, int(max_new),
                              deadline_abs, now, priority=priority,
                              tenant=tenant, sampling=samp,
                              adapter=adapter)
                self._c_failed.inc()
                self._finalize(rec, RequestState.FAILED, "error", [],
                               error="deferred admission failed: %s: %s"
                               % (type(e).__name__, str(e)[:200]))
                return
            # a deferred AUTO submit's identity exists from HERE: the
            # pool just assigned it, and the stream handle learns it
            # before any token can flow
            stream.request_id = rid
            rec = _Record(rid, stream, ids, int(max_new), deadline_abs,
                          now, priority=priority, tenant=tenant,
                          sampling=samp, adapter=adapter)
            self._live[rid] = rec
            if self._journal is not None:
                try:
                    # the admit record's deadline_s is the budget
                    # REMAINING at this admission (the anchor already
                    # absorbed the restore wait), stamped like any
                    # other admit so a later restore keeps deducting
                    self._journal_admit(
                        rid, ids, max_new,
                        (None if deadline_abs is None
                         else max(0.001, deadline_abs - now)),
                        priority, tenant, sampling=samp,
                        adapter=adapter)
                except Exception as e:  # noqa: BLE001 - to the stream
                    self._pool.cancel(rid)
                    self._live.pop(rid, None)
                    self._c_failed.inc()
                    self._finalize(
                        rec, RequestState.FAILED, "error", [],
                        error="deferred admission not journalable: %s"
                        % (str(e)[:200],))
                    return
            self._c_submitted.inc()
            trace.instant("req.queued", rid=rid, deferred=True,
                          prompt_tokens=int(ids.shape[0]),
                          max_new_tokens=int(max_new))

    @staticmethod
    def _fingerprint_upgrade(fp: dict, mine: dict):
        """v1→v2 journal upgrade triage (docs/DESIGN.md §5q).

        A v1 header's fingerprint carries pool-GLOBAL sampling scalars
        (``temperature``/``top_k``/``top_p``/``sampling_seed``) where a
        v2 fingerprint carries the ``"sampling": "per-request"`` marker
        plus the LoRA bank geometry.  When the two agree on EVERY other
        field — and this engine serves the base model only (a v1 writer
        cannot have journaled adapter ids) — the journal is adoptable:
        every live entry replays through the prompt+committed resubmit
        fallback with the old global config applied per-request.
        Returns that config as a :class:`_SamplingConfig`, or None when
        the journals genuinely disagree (the caller then raises the
        normal mismatch error)."""
        v1_keys = ("temperature", "top_k", "top_p", "sampling_seed")
        if "sampling" in fp or not all(k in fp for k in v1_keys):
            return None
        if mine.get("sampling") != "per-request" \
                or mine.get("lora") is not None:
            return None
        rest = {k: v for k, v in fp.items() if k not in v1_keys}
        mine_rest = {k: v for k, v in mine.items()
                     if k not in ("sampling", "lora")}
        if rest != mine_rest:
            return None
        return _SamplingConfig(
            float(fp["temperature"]), int(fp["top_k"]),
            float(fp["top_p"]), int(fp["sampling_seed"]) & 0xFFFFFFFF)

    # -- multi-LoRA adapter management (docs §5q) ------------------------
    def load_adapter(self, idx: int, weights: dict) -> None:
        """Hot-load adapter ``idx``'s low-rank weights into the pool's
        stacked bank — an in-place device write under the engine lock,
        never a recompile; in-flight requests on other adapter rows are
        untouched (their ids index unchanged rows)."""
        with self._lock:
            self._settle_pool()
            self._pool.load_adapter(idx, weights)

    def unload_adapter(self, idx: int) -> None:
        """Zero adapter ``idx``'s bank row; refuses (typed) while any
        live request is pinned to it."""
        with self._lock:
            self._settle_pool()
            self._pool.unload_adapter(idx)

    def has_adapter(self, idx: int) -> bool:
        """Whether ``idx`` is servable here: 0 (base) always; a
        nonzero id needs an attached bank with that row.  The fleet
        router keys adapter-aware placement off this."""
        try:
            self._pool._check_adapter(idx)
        except InvalidArgumentError:
            return False
        return True

    @property
    def lora_config(self):
        """The pool's attached bank geometry ``(n_adapters, rank)``,
        or None (base model only)."""
        return self._pool.lora_config

    def restore(self, path: str) -> dict:
        """Adopt the journal at ``path``: validate its fingerprint
        against this engine (typed mismatch error naming both sides),
        truncate-tolerantly replay it, and reconstruct every live
        request — PREEMPTED requests whose disk-spill file is present
        and exact are re-parked in the spill tier (their K/V page back
        in at resume, no re-prefill), everything else resubmits
        prompt + committed through the ``_recover`` machinery, so every
        greedy survivor finishes byte-identically with ZERO new
        compiles on warmed executables.  Requests whose journaled
        history already exhausted their budget (torn tail ate the
        terminal record) finalize immediately.

        The engine must be fresh (no live requests); while the replay
        runs the engine is RESTORING — ``/healthz`` 503 + Retry-After,
        submits deferred.  With a configured journal the live set is
        checkpoint-compacted into it afterwards, so a second crash
        replays from HERE, not from the adopted file.  Returns the
        summary dict (``requests_replayed``, ``tokens_replayed``,
        ``adopted_from_spill``, ``finished_at_restore``,
        ``records``, ``records_dropped``, ``restore_s``)."""
        t0 = time.perf_counter()
        with self._lock:
            # precondition check and the RESTORING flip happen under
            # ONE lock acquisition: a gap between them would let a
            # concurrent submit admit into the pool mid-restore and
            # collide with a replayed survivor's rid
            if self._draining:
                raise PreconditionNotMetError(
                    "engine is draining/shut down: build a fresh engine "
                    "to restore into")
            if self._restoring:
                raise PreconditionNotMetError(
                    "a restore is already in progress on this engine: "
                    "a second concurrent replay would fail every "
                    "duplicate resubmit and journal bogus terminals "
                    "for requests the first replay is serving")
            if self._live or self._pool.queue_depth \
                    or self._pool.active_count:
                raise PreconditionNotMetError(
                    "restore() needs a fresh engine: %d live requests "
                    "are already being served (restore rebuilds the "
                    "live set from the journal, it does not merge)"
                    % (len(self._live),))
            self._restoring = True
            self._restore_retry_after_s = 1.0
        adopted = finished = replayed = tokens_replayed = 0
        try:
            with self._lock:
                fp, records, stats = read_journal(path)
                if stats["truncated"]:
                    self._c_journal_truncated.inc(
                        stats["records_dropped"])
                    trace.instant(
                        "journal.truncated",
                        dropped_records=stats["records_dropped"],
                        dropped_bytes=stats["bytes_dropped"])
                    slog.emit(
                        "journal.truncated", path=path,
                        dropped_records=stats["records_dropped"],
                        dropped_bytes=stats["bytes_dropped"])
                mine = self._pool.config_fingerprint()
                legacy_samp = None
                if fp != mine:
                    # v1→v2 upgrade triage (docs §5q): a v1 journal
                    # that matches modulo the sampling fields replays
                    # through the resubmit fallback with its old
                    # GLOBAL config applied per-request; any other
                    # mismatch still refuses, naming both sides
                    legacy_samp = self._fingerprint_upgrade(fp, mine)
                    if legacy_samp is None:
                        raise FingerprintMismatchError(fp, mine)
                    slog.emit("journal.upgrade", path=path,
                              temperature=legacy_samp.temperature,
                              top_k=legacy_samp.top_k,
                              top_p=legacy_samp.top_p,
                              seed=legacy_samp.seed)
                live, counts = replay(records)
                now = self._clock()
                for entry in live:
                    rid = entry["rid"]
                    ids = np.asarray(entry["ids"], np.int32)
                    toks = entry["tokens"]
                    max_new = entry["max_new"]
                    deadline_s = entry["deadline_s"]
                    if deadline_s is not None and entry.get("ts"):
                        # REMAINING budget, not a fresh grant: deduct
                        # the wall-clock time already burned since
                        # admission (checkpoint entries carry the
                        # remaining budget directly, ts=None).  An
                        # exhausted deadline re-arms at epsilon so the
                        # first tick expires it, same as checkpoint's
                        # floor
                        deadline_s = max(
                            0.001, float(deadline_s)
                            - max(0.0, time.time() - entry["ts"]))
                    deadline_abs = None if deadline_s is None \
                        else now + float(deadline_s)
                    msamp = entry.get("sampling")
                    if msamp is not None:
                        samp = _samp_from_json(msamp)
                    elif legacy_samp is not None:
                        # v1 entry: the old pool-global config, with a
                        # per-request seed offset so replayed sampled
                        # streams stay distinct (v1's batch-positional
                        # key chain is unrecoverable — the upgrade
                        # contract is deterministic-going-forward via
                        # the resubmit fallback, not byte-identity
                        # with the crashed v1 engine)
                        samp = legacy_samp._replace(
                            seed=(legacy_samp.seed + replayed)
                            & 0xFFFFFFFF)
                    else:
                        samp = None
                    stream = ResponseStream(self, rid, max_new)
                    rec = _Record(rid, stream, ids, max_new,
                                  deadline_abs, now,
                                  priority=entry["priority"],
                                  tenant=entry["tenant"],
                                  sampling=samp,
                                  adapter=int(entry.get("adapter")
                                              or 0))
                    rec.retries = entry["retries"]
                    rec.tokens = list(toks)
                    # the committed history replays into the FRESH
                    # stream, so a consumer of this engine sees the
                    # full token stream, not just the post-restore tail
                    for t in toks:
                        stream._put_token(int(t))
                    if toks:
                        rec.first_t = rec.last_t = now
                    self._c_replayed.inc()
                    replayed += 1
                    tokens_replayed += len(toks)
                    reason = self._complete_reason(toks, max_new)
                    if reason is not None:
                        # budget exhausted / EOS committed but the
                        # terminal record was lost to the torn tail:
                        # the request is DONE, finish it here instead
                        # of resubmitting work the contract forbids
                        self._c_done.inc()
                        self._finalize(rec, RequestState.DONE, reason,
                                       rec.tokens)
                        finished += 1
                        continue
                    if legacy_samp is None and self._pool.adopt_spill(
                            rid, ids, toks, max_new,
                            priority=entry["priority"],
                            tenant=entry["tenant"],
                            deadline=deadline_abs):
                        # (v1 journals skip the spill fast path: their
                        # spill files predate the per-request sampling
                        # meta — the resubmit fallback IS the upgrade
                        # path)
                        # the crashed engine's disk-spilled K/V are
                        # exact for this committed count: re-park the
                        # request — it resumes via page-in, skipping
                        # the re-prefill entirely
                        rec.state = RequestState.PREEMPTED
                        rec.preempted_at = now
                        self._live[rid] = rec
                        adopted += 1
                        continue
                    try:
                        self._resubmit_record(rec)
                    except Exception as e:  # noqa: BLE001 - per-victim
                        self._fail_record(rec, e,
                                          "restore resubmit failed")
                        continue
                    self._live[rid] = rec
                self._c_restores.inc()
                restore_s = time.perf_counter() - t0
                self._health.note_restore(restore_s)
                if self._journal is not None:
                    # compact the adopted state into THIS engine's
                    # journal: a second crash replays from here
                    self.checkpoint()
                trace.instant("engine.restore", replayed=replayed,
                              adopted=adopted, finished=finished,
                              tokens=tokens_replayed)
                slog.emit("engine.restore", path=path,
                          requests_replayed=replayed,
                          adopted_from_spill=adopted,
                          finished_at_restore=finished,
                          tokens_replayed=tokens_replayed,
                          records=stats["records"],
                          records_dropped=stats["records_dropped"],
                          restore_s=round(restore_s, 6))
        finally:
            self._end_restore()
        self._wake.set()
        return {"requests_replayed": replayed,
                "adopted_from_spill": adopted,
                "finished_at_restore": finished,
                "tokens_replayed": tokens_replayed,
                "records": stats["records"],
                "records_dropped": stats["records_dropped"],
                "truncated": stats["truncated"],
                "journal_counts": counts,
                "restore_s": time.perf_counter() - t0}

    # -- the scheduling tick (ONE code path for both drive modes) --------
    def _tick(self) -> bool:
        tr = trace.active()
        if tr is None:
            return self._run_tick(None)
        return self._run_tick_traced(tr)

    def _run_tick_traced(self, tr) -> bool:
        """The traced twin of the tick: same ``_run_tick`` body inside a
        numbered ``tick`` span that says what the tick did (``queued``
        at its start, requests ``admitted`` to a slot and ``finished``
        inside it) and how often its thread was switched out inside it
        (``nvcsw`` by itself: it blocked; ``nivcsw`` by the machine: its
        core was taken; absent where the platform counts neither by
        thread), plus compile-event diffing and the drop-counter
        mirror.  All tracer bookkeeping writes re-take the
        (reentrant) engine lock the driving thread already holds, so the
        lock discipline stays textual."""
        if tr is not self._tracer:
            with self._lock:
                self._tracer = tr
                self._trace_dropped_seen = 0
                self._compile_seen = None
        if self._compile_seen is None:
            with self._lock:
                # baseline BEFORE the tick so a cold engine's very first
                # traced tick reports its own compiles as events
                self._compile_seen = self._pool.compile_counts()
        with tr.span("tick", tick=tr.next_tick(),
                     queued=self._pool.queue_depth) as span:
            admitted, finished = self._n_admitted, self._n_finalized
            before = trace.thread_switches()
            work = self._run_tick(tr)
            span.set(admitted=self._n_admitted - admitted,
                     finished=self._n_finalized - finished)
            if before is not None:
                nvcsw, nivcsw = trace.thread_switches()
                span.set(nvcsw=nvcsw - before[0], nivcsw=nivcsw - before[1])
        counts = self._pool.compile_counts()
        if counts != self._compile_seen:
            for key, n in counts.items():
                if n != self._compile_seen.get(key):
                    tr.instant("compile", what=key, count=int(n))
            with self._lock:
                self._compile_seen = counts
        dropped = tr.recorder.dropped
        if dropped > self._trace_dropped_seen:
            self._c_trace_dropped.inc(dropped - self._trace_dropped_seen)
            with self._lock:
                self._trace_dropped_seen = dropped
        return work

    def _run_tick(self, tr) -> bool:
        """One tick.  ``tr`` is the installed tracer or None: the tick's
        own work around ``pool.step()`` is spanned the pool's way
        (``tick_phase``: a shared no-op when tracing is off) —
        ``tick.govern`` (deadlines, the degradation ladder),
        ``tick.observe`` (handoff sweep, gauges; its meta ``refreshed``
        is 1 when the cache gauges were recomputed, else 0),
        ``tick.journal`` (flush, SLO roll, heartbeat)."""
        self._health.note_tick_start(self._clock())
        try:
            with tick_phase(tr, "tick.govern"):
                self._govern()
            if not self._live:
                self._observe(tr)
                return False
            self._h_queue.observe(self._pool.queue_depth)
            try:
                with self._timer:
                    self._pool.step()
            except Exception as e:  # noqa: BLE001 - step is the blast radius
                self._health.note_error(self._clock(), e,
                                        faults.classify_error(e))
                self._recover(e)
            self._observe(tr)
            return bool(self._live)
        finally:
            with tick_phase(tr, "tick.journal"):
                self._close_tick()

    def _govern(self) -> None:
        self._expire()
        # ladder BEFORE the pool step: it reads the alert state the
        # previous tick's window roll produced, and a preemption it
        # performs frees capacity THIS tick's refill can hand to
        # waiting high-priority work — and it must also run on idle
        # ticks, or a drained engine could never step back up
        self._degrade_eval()

    def _observe(self, tr) -> None:
        with tick_phase(tr, "tick.observe") as span:
            # prefill-role tick edge: export every prefill that
            # completed this step and hand it off (no-op otherwise)
            self._export_sweep()
            refreshed = self._observe_gauges()
            if span is not None:
                span.set(refreshed=int(refreshed))

    def _close_tick(self) -> None:
        # the tick's journal flush rides the tick's finally: commits
        # and terminals from a recovered tick are recorded too, and
        # a flush failure leaves records PENDING — the journal
        # falls behind, the engine never dies for it
        self._journal_flush()
        # the heartbeat closes even when recovery re-raises: the
        # loop thread dying is the DEAD-LOOP signal, not a stall —
        # and the SLO windows roll on EVERY tick (idle included),
        # so an alert drains while the engine sits healthy-idle
        if self._slo is not None:
            self._slo.note_tick()
        self._health.note_tick_end(self._clock())

    def _observe_gauges(self) -> bool:
        """Set the gauges a tick; True when the cache gauges were among
        them.  Those are recomputed only when the allocator changed
        (``alloc_version``): between two of its events ``cache_stats()``
        returns what it returned, and the steady-state price is one int
        compare, as for the cost gauges below."""
        pool = self._pool
        self._g_queue.set(pool.queue_depth)
        self._g_active.set(pool.active_count)
        self._g_occupancy.set(pool.active_count / pool.slots)
        version = pool.alloc_version()
        refreshed = version != self._alloc_seen
        if refreshed:
            self._alloc_seen = version
            self._observe_cache_gauges(pool.cache_stats())
        self._g_preempted.set(pool.preempted_count)
        if self._g_accept is not None:
            self._g_accept.set(
                pool.acceptance_stats()["acceptance_rate"])
        # the pool's totals only grow: a counter moves by what its
        # total gained since the counter last read it
        self._c_drawing.inc(pool.steps_drawing - self._c_drawing.value)
        if self._c_loop_passes is not None:
            self._c_loop_passes.inc(
                pool.loop_passes - self._c_loop_passes.value)
        if self._c_window_lapped is not None:
            self._c_window_lapped.inc(pool.window_blocks_overwritten
                                      - self._c_window_lapped.value)
        if self._c_block is not None:
            for key, now in pool.block_stats().items():
                self._c_block[key].inc(now - self._c_block[key].value)
        if self._g_prefix_hit is not None or self._c_chunks is not None:
            pstats = pool.prefix_stats()
            if self._g_prefix_hit is not None:
                self._g_prefix_hit.set(pstats["hit_rate"])
                self._g_prefix_shared.set(pstats["blocks_shared_now"])
            if self._c_chunks is not None:
                # counter semantics on /metrics: increment by the
                # pool's delta since the last tick (the pool keeps the
                # cumulative host-side count)
                total = pstats["prefill_chunks_total"]
                if total > self._chunks_seen:
                    self._c_chunks.inc(total - self._chunks_seen)
                    self._chunks_seen = total
        if self._timer.total:
            self._g_tps.set(self._tokens_total / self._timer.total)
            self._g_step.set(self._timer.step_time)
        # cost gauges refresh only when the executable set changed
        # (a compile): the steady-state price is one int compare
        version = pool.cost_version()
        if version != self._cost_seen:
            self._cost_seen = version
            derived = pool.cost_report().get("derived") or {}
            if derived:
                self._g_step_flops.set(derived.get("step_flops", 0.0))
                self._g_step_bytes.set(
                    derived.get("step_bytes_accessed", 0.0))
                self._g_hbm_reserved.set(
                    derived.get("hbm_reserved_bytes") or 0.0)
        return refreshed

    def _observe_cache_gauges(self, stats: dict) -> None:
        """The gauges read off ``cache_stats()``: the K/V and state
        bytes, the layers by layout, free, per-shard and spilled
        blocks."""
        self._c_gauge_refreshes.inc()
        self._g_kv_bytes.set(stats["reachable_bytes"])
        self._g_kv_resident.set(stats["pool_bytes"])
        if self._g_state_slot is not None:
            self._g_state_slot.set(stats["bytes_per_slot"]["recurrent"])
        for kind, g in self._g_cache_entries.items():
            g.set(stats["cache_entries"][kind])
        if self._g_cache_planes is not None:
            self._g_cache_planes.set(stats["cache_planes"])
        if self._g_experts_read is not None:
            self._g_experts_read.set(stats["experts_read_expected"])
        if self._g_kv_free is not None:
            self._g_kv_free.set(stats["free_blocks"])
        if self._g_kv_resident_shard is not None:
            self._g_mesh_devices.set(stats["mesh"]["devices"])
            per_shard = stats["per_shard"]
            self._g_kv_resident_shard.set(per_shard[0]["pool_bytes"])
            self._g_kv_reachable_shard.set(
                max(s["reachable_bytes"] for s in per_shard))
        if self._g_spilled_blocks is not None:
            self._g_spilled_blocks.set(stats["spilled_blocks"])

    # -- drive mode 1: synchronous pump (deterministic, tests) -----------
    def pump(self, steps: int = 1) -> bool:
        """Run up to ``steps`` scheduling ticks INLINE on the calling
        thread; True while live requests remain.  The deterministic
        drive mode: no thread, no sleeps, every test single-threaded.
        Refuses when the background loop owns the engine."""
        if self._thread is not None:
            raise PreconditionNotMetError(
                "the engine owns a background step loop (start() was "
                "called); pump() is the synchronous drive mode — don't "
                "mix them")
        if int(steps) < 1:
            raise InvalidArgumentError(
                "pump needs steps >= 1, got %r" % (steps,))
        work = bool(self._live)
        for _ in range(int(steps)):
            with self._lock:
                work = self._tick()
            if not work:
                break
        return work

    # -- drive mode 2: owned background step loop (real serving) ---------
    def start(self) -> "ServingEngine":
        """Spawn the owned step-loop thread; returns self.  The loop
        runs the same ``_tick`` as ``pump()`` and parks on an event when
        idle (a submit wakes it)."""
        with self._lock:
            if self._thread is not None:
                return self
            if self._draining:
                # a restarted loop would park forever on an engine that
                # refuses every submit; admissions cannot be re-opened
                raise PreconditionNotMetError(
                    "engine was drained/shut down; build a new "
                    "ServingEngine instead of restarting this one")
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="serving-engine-step-loop",
                daemon=True)
            self._thread.start()
        return self

    def is_running(self) -> bool:
        """True when the background step loop owns the engine."""
        return self._thread is not None

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                with self._lock:
                    work = self._tick()
            except Exception as e:  # noqa: BLE001
                # _tick's recovery already failed the live requests;
                # record WHAT killed the tick and WHEN into health() so
                # the parked loop is a post-mortem, not a mystery —
                # and ship the flight recorder's tail with it
                with self._lock:
                    self._health.note_error(self._clock(), e, "loop")
                    self._dump_flight("loop-error")
                work = False
            # submit and cancel get their turn between two ticks; the
            # step in flight covers the wait (at most the idle wait's)
            self._callers.let_in(0.002)
            if not work:
                self._wake.wait(0.002)
                self._wake.clear()

    def restart_loop(self) -> bool:
        """Supervisor entry point: replace a DEAD background loop with a
        fresh one (counted in ``serving_engine_restarts_total``).  False
        — with no side effects — while the old thread is still alive
        (a live loop must not be doubled), when no loop was ever
        started, or once draining/shutdown made restarts pointless."""
        with self._lock:
            t = self._thread
            if t is None or t.is_alive() or self._draining \
                    or self._stop.is_set():
                return False
            t.join(timeout=0)
            self._thread = threading.Thread(
                target=self._loop, name="serving-engine-step-loop",
                daemon=True)
            self._thread.start()
            self._c_restarts.inc()
            self._health.note_restart(self._clock())
            trace.instant("restart")
            slog.emit("engine.restart")
        self._wake.set()
        return True

    def _note_stall(self) -> None:
        """Supervisor hook: one stall EPISODE was opened on this
        engine's heartbeat (the supervisor already de-duplicated
        polls)."""
        self._c_stalled.inc()
        trace.instant("stall")
        slog.emit("engine.stall")

    def _dump_flight(self, reason: str) -> None:
        """Attach the flight recorder's tail to the health record so
        the post-mortem (``health()`` / ``GET /healthz``) ships its own
        timeline.  No-op when no tracer was ever active."""
        tr = trace.active() or self._tracer
        if tr is not None:
            self._health.note_flight_dump(self._clock(), reason,
                                          tr.recorder.tail_dicts(),
                                          trace_now=tr.now())

    def health(self) -> dict:
        """Liveness/post-mortem snapshot — the ``GET /healthz`` body.

        Deliberately LOCK-FREE: a wedged tick is holding the engine
        lock, and health is exactly the question asked during a wedge.
        Every field is a single-writer plain attribute (see
        ``supervisor.EngineHealth``); a torn read costs staleness,
        never a hang.  ``healthy`` is False while a stall episode is
        open, while a started loop is dead, and after drain/shutdown."""
        h = self._health
        t = self._thread
        loop_alive = None if t is None else t.is_alive()
        if h.stall_open:
            state = "wedged"
        elif self._restoring:
            # RESTORING is unhealthy-but-transient: the probe backs off
            # (503 + Retry-After on /healthz) instead of killing an
            # engine that is seconds from adopting its journal —
            # admissions are deferred meanwhile, never dropped
            state = "restoring"
        elif loop_alive is False and not self._draining \
                and not self._stop.is_set():
            state = "loop-dead"
        elif self._draining:
            state = "draining" if self._live else "stopped"
        elif self._live:
            state = "serving"
        else:
            state = "idle"
        now = self._clock()
        out = {"state": state,
               "healthy": state in ("idle", "serving", "draining"),
               "role": self.role,
               "live_requests": len(self._live),
               "queue_depth": self._pool.queue_depth,
               "loop_alive": loop_alive,
               "draining": self._draining,
               # degradation is the system WORKING, not wedging: a
               # degraded-but-serving engine stays healthy/200 — the
               # probe reads the level and the parked-victim count
               # here, while 503 stays reserved for wedged/loop-dead/
               # stopped (test-pinned)
               "degraded": self._degrade_level,
               "preempted_requests": self._pool.preempted_count,
               # birth + age on the engine's monotonic clock: a probe
               # distinguishes "just restarted" from "long-lived" at a
               # glance, and uptime_s is injected-clock-deterministic
               "started_at": self._started_at,
               "uptime_s": max(0.0, now - self._started_at),
               "restoring": self._restoring}
        if self._restoring:
            out["retry_after_s"] = self._restore_retry_after_s
        if self._slo is not None:
            # SLO state rides the post-mortem: a stall dump says which
            # promises were burning when the engine wedged
            out["slo"] = self._slo.health_summary()
        out.update(h.snapshot())
        return out

    def _deadline_estimate_s(self, max_new_tokens: int,
                             prompt_len: int = 0) -> Optional[float]:
        """Seconds until a request admitted NOW would finish, from the
        observed mean tick time and the live token backlog — None until
        a tick has been measured (the engine never sheds on a guess).
        The model is the pool's own behavior: each tick advances every
        slot one token, so the backlog drains at ``slots`` tokens per
        tick and the new request then needs ``max_new_tokens`` ticks of
        its own.  Under chunked prefill, prompt work is ALSO tick work
        the token backlog cannot see: chunks run ONE SLOT PER TICK
        (``_chunk_work`` is FIFO-serialized), so each not-yet-decoding
        prompt (plus this request's own) contributes its OWN
        ``ceil(len/C)`` ticks — per-request ceils, never one ceil over
        the summed lengths: ten queued 5-token prompts at C=16 cost
        ten serialized chunk ticks where the summed form would claim
        one, and exactly that under-estimate let bursty long-prompt
        arrivals admit-then-expire instead of shedding at admission.
        Deliberately simple and stated here so the shed decision is
        auditable from the error message."""
        if not self._timer.total:
            return None
        step_s = self._timer.step_time
        backlog = sum(r.max_new - len(r.tokens)
                      for r in self._live.values())
        ticks = backlog / self._pool.slots + float(max_new_tokens)
        chunk = getattr(self._pool, "prefill_chunk_tokens", None)
        if chunk:
            # not-yet-decoding = state QUEUED/PREFILLING, not
            # first_t-is-None: a recovery-resubmitted victim already
            # streamed tokens (first_t set) but still owes a FULL
            # re-prefill of prompt + committed through the chunk path
            pending = [prompt_len] + [
                r.prompt_len + len(r.tokens)
                for r in self._live.values()
                if r.state in (RequestState.QUEUED,
                               RequestState.PREFILLING)]
            ticks += sum(-(-p // chunk) for p in pending if p)
        return step_s * ticks

    # -- graceful teardown ----------------------------------------------
    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Stop admissions, finish every in-flight request.  True when
        drained; False on timeout — honored in BOTH drive modes (pump
        mode checks the wall clock between inline ticks).  Admissions
        stay closed after a timed-out drain; call again to keep
        waiting."""
        with self._lock:
            self._draining = True
        if self._thread is None:
            deadline = None if timeout_s is None \
                else time.monotonic() + timeout_s
            while self.pump(1):
                if deadline is not None and \
                        time.monotonic() >= deadline:
                    return False
            return True
        # the poll deadline uses REAL time on purpose: an injected
        # clock (deadline tests) governs request deadlines, but how
        # long the caller is willing to block is a wall-clock matter
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        self._wake.set()
        while True:
            with self._lock:
                if not self._live:
                    return True
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(0.002)

    def shutdown(self, drain: bool = True) -> None:
        """Graceful stop: admissions off, in-flight requests finished
        (``drain=True``) or cancelled (``drain=False``), background
        thread joined."""
        with self._lock:
            self._draining = True
        if drain:
            self.drain()
        else:
            with self._lock:
                for rid in list(self._live):
                    self.cancel(rid)
        t = self._thread  # snapshot: a concurrent shutdown may null it
        if t is not None:
            self._stop.set()
            self._wake.set()
            t.join(timeout=10.0)
            # the handle write goes back under the lock: a concurrent
            # start()/pump() reads _thread to decide the drive mode.
            # Only after a SUCCESSFUL join — a wedged tick outlives the
            # join timeout still holding the lock, and acquiring it
            # here would turn the bounded 10 s shutdown into an
            # unbounded hang (tools/analysis lock-discipline)
            if not t.is_alive():
                with self._lock:
                    if self._thread is t:
                        self._thread = None
        with self._lock:
            # a drain that wedged left records live: close their TRACE
            # timelines (terminal mark only — the streams stay as they
            # are, the engine is stopped) so an export after shutdown
            # never ends a request track mid-span.  Normal shutdowns
            # have no leftovers: drain finishes requests and
            # drain=False cancels them, both through _finalize.
            for rid in list(self._live):
                trace.instant("req.aborted", rid=rid, reason="shutdown")
            # final durability point: drain buffered journal records and
            # close the handle (a clean shutdown's journal replays to an
            # empty or fully-terminal live set)
            self._journal_flush()
            if self._journal is not None:
                self._journal.close()

    # -- tracing / flight recorder ---------------------------------------
    def start_trace(self, capacity: int = 4096) -> "trace.Tracer":
        """Build + install a process-wide tracer (serving/trace.py) and
        bind it to this engine for export; returns it.  Refuses to
        stack on an already-installed tracer."""
        t = trace.Tracer(capacity=capacity)
        trace.install(t)
        with self._lock:
            self._tracer = t
            self._trace_dropped_seen = 0
            self._compile_seen = None
        return t

    def stop_trace(self) -> Optional["trace.Tracer"]:
        """Uninstall the process-wide tracer (idempotent when none is
        active); returns the tracer that was active, whose recorder
        stays exportable through this engine.  Refuses to kill ANOTHER
        engine's tracer: in a multi-engine process, stop the trace from
        the engine that owns it (or via ``serving.trace.uninstall()``
        when you really mean process-wide)."""
        t = trace.active()
        if t is not None and t is not self._tracer:
            # covers both a diverged tracer AND an engine that never
            # traced at all — either way the live tracer belongs to
            # someone else and must not be silently killed
            raise PreconditionNotMetError(
                "the installed tracer is not this engine's: stop it "
                "from the engine that started it (a manually installed "
                "tracer is adopted by the first traced tick), or call "
                "serving.trace.uninstall() to stop tracing "
                "process-wide")
        trace.uninstall()
        return t

    def _trace_source(self) -> "trace.Tracer":
        tr = trace.active() or self._tracer
        if tr is None:
            raise PreconditionNotMetError(
                "no tracer was ever active on this engine: call "
                "start_trace() (or serving.trace.install) and run "
                "traffic before exporting a timeline")
        return tr

    def export_chrome_trace(self, path: Optional[str] = None) -> str:
        """Chrome/Perfetto trace-event JSON of the flight recorder —
        one track per request (lifecycle spans closed by the terminal
        mark) and one per tick phase.  Returns the JSON string; also
        writes ``path`` when given.  Exports the ACTIVE tracer, falling back
        to the last tracer this engine saw (so export-after-stop
        works)."""
        return trace.export_chrome_trace(
            self._trace_source().recorder.snapshot(), path=path)

    def request_trace(self, request_id) -> dict:
        """One request's timeline as plain JSON-safe dicts — the
        ``GET /debug/trace?rid=<id>`` body.  String forms of the id
        match too (HTTP query params arrive as strings); unknown ids
        raise :class:`NotFoundError`."""
        events = [e for e in self._trace_source().recorder.snapshot()
                  if e.rid is not None and (
                      e.rid == request_id
                      or str(e.rid) == str(request_id))]
        if not events:
            raise NotFoundError(
                "no trace events recorded for request_id %r (unknown "
                "id, or its events were evicted by the ring — see "
                "serving_trace_events_dropped_total)" % (request_id,))
        return {"request_id": request_id,
                "events": [e.to_dict() for e in events]}

    def flight_recorder(self) -> dict:
        """The flight recorder's full state as JSON-safe dicts — the
        ``GET /debug/flightrec`` body: capacity, drop count, and every
        retained event oldest-first."""
        tr = self._trace_source()
        rec = tr.recorder
        return {"capacity": rec.capacity,
                "dropped": rec.dropped,
                "total_events": rec.total_events,
                "events": [e.to_dict() for e in rec.snapshot()]}

    # -- passthroughs / introspection ------------------------------------
    def refresh_weights(self) -> None:
        """Hot weight swap between steps: drop the pool's cached
        parameter values so the next decode step reads the model's
        current weights (call after ``set_state_dict``)."""
        with self._lock:
            self._settle_pool()
            self._pool.refresh_weights()
            trace.instant("weights.refresh")

    def compile_counts(self) -> dict:
        """The pool's compile accounting — the exactly-two-compiles
        contract survives the serving layer (pinned by tests)."""
        return self._pool.compile_counts()

    def cache_stats(self) -> dict:
        """Live KV accounting (``GenerationPool.cache_stats``)."""
        return self._pool.cache_stats()

    def cost_report(self) -> dict:
        """Per-executable cost/memory attribution read off the pool's
        compiled artifacts (``GenerationPool.cost_report`` /
        ``SpeculativePool.cost_report``): optimized-HLO FLOPs and
        bytes-accessed, the ``memory_analysis()`` HBM breakdown, the
        decode step's ``kv_cache_bytes``, and the ``derived`` per-token
        cost model behind the ``serving_step_*`` gauges.  A read of
        compile-time analysis — never a compile, never a device sync
        (compile counts before and after are identical, test-pinned)."""
        return self._pool.cost_report()

    def slo_snapshot(self) -> dict:
        """The SLO tracker's full state — the ``GET /slo`` body.
        Raises :class:`PreconditionNotMetError` when the engine was
        built without objectives (``slo=None``)."""
        if self._slo is None:
            raise PreconditionNotMetError(
                "no SLO tracker is configured on this engine: pass "
                "slo=serving.slo.SLOTracker([...objectives...]) at "
                "construction to declare objectives")
        snap = self._slo.snapshot()
        # the closed loop rides the same body: what the alert is
        # currently MAKING the engine do (docs §5j)
        snap["degradation"] = self.degradation_snapshot()
        return snap

    @property
    def slo(self):
        """The engine's :class:`~.slo.SLOTracker` (None when SLO
        tracking is off)."""
        return self._slo

    def prefix_stats(self) -> dict:
        """Prefix-sharing / chunked-prefill accounting
        (``GenerationPool.prefix_stats``): hit rate, matched tokens /
        blocks, live shared blocks, chunk totals — what the
        ``serving_prefix_*`` gauges read."""
        return self._pool.prefix_stats()

    def resident_prefix_digest(self, since_epoch=None):
        """Chain-hash digest of the K/V blocks resident in this
        engine's prefix index (``GenerationPool.prefix_digest``) — the
        affinity signal the fleet router hashes prompt heads against.
        Epoch-cached: pass the previous digest's ``epoch`` and an
        unchanged index returns without the key set.  None when prefix
        sharing is off."""
        with self._lock:
            return self._pool.prefix_digest(since_epoch)

    def reset_prefix_stats(self) -> None:
        """Zero the pool's cumulative prefix/chunk counters: a caller
        that warms the engine calls this so the hit rate covers only
        the traffic after it."""
        with self._lock:
            self._pool.reset_prefix_stats()
            # the chunk-counter watermark must restart with the pool's
            # count: left at its old high-water mark, the next chunks
            # up to it would never reach serving_prefill_chunks_total
            self._chunks_seen = 0

    def spill_stats(self) -> dict:
        """Host-RAM spill-tier accounting
        (``GenerationPool.spill_stats``): preempt/resume totals, parked
        requests, device-resident spilled blocks vs host-only copies,
        spill/upload byte totals — what the ``serving_spilled_*``
        gauges read."""
        return self._pool.spill_stats()

    def acceptance_stats(self) -> Optional[dict]:
        """Speculative acceptance accounting
        (``SpeculativePool.acceptance_stats``); None on a plain pool."""
        if hasattr(self._pool, "acceptance_stats"):
            return self._pool.acceptance_stats()
        return None

    def request_state(self, request_id) -> Optional[str]:
        """Lifecycle state of a LIVE request (terminal states live on
        the stream's status record); None if unknown/terminal."""
        with self._lock:
            rec = self._live.get(request_id)
            return rec.state if rec is not None else None

    @property
    def queue_depth(self) -> int:
        return self._pool.queue_depth

    @property
    def live_requests(self) -> int:
        return len(self._live)

    @property
    def draining(self) -> bool:
        return self._draining
