"""``paddle_tpu.serving`` — the request scheduler over the decode engine.

The serving stack, bottom to top (docs/DESIGN.md §5a-§5c):

- ``jit.DecodeSession`` — exactly-two-compiles prefill/decode split;
- ``inference.GenerationPool`` — slot-based continuous batching, paged
  KV with a free-list allocator;
- **this package** — the entry point the ROADMAP north star needs:
  request lifecycle + streaming (``ServingEngine.submit`` →
  ``ResponseStream``), per-request deadlines, bounded-queue admission
  control (typed, retryable ``QueueFullError``), mid-generation
  cancellation that frees slots and paged blocks, graceful
  drain/shutdown, hot weight swap, and a serving metrics registry
  (TTFT, inter-token latency, queue depth, occupancy, tokens/s) with
  prometheus text exposition.

Fault tolerance (docs/DESIGN.md §5f): a failed ``pool.step()`` has a
REQUEST-level blast radius — the engine rebuilds the pool and resubmits
each victim's prompt+committed tokens, so greedy survivors continue
token-identically, with typed transient-vs-permanent classification and
a bounded per-request retry budget.  ``faults`` is the deterministic
injection plane (named seams, scripted schedules, seeded chaos mode —
a module-level no-op when off); ``Supervisor`` is the watchdog that
restarts a dead loop and flags wedged ticks; ``ServingEngine.health()``
backs ``GET /healthz``; deadline-aware admission sheds unattainable
requests with the retryable ``DeadlineUnattainableError``.

Observability (docs/DESIGN.md §5g): ``metrics`` is the aggregate
surface, ``supervisor`` the liveness surface, and ``trace`` the
request-scoped one — a bounded flight recorder plus span/event tracing
of the full request path (lifecycle transitions, tick phases, compile
events, fault injections, recoveries, sheds, restarts), a module-level
no-op when off; every span is also a ``jax.profiler.TraceAnnotation``,
so a profile taken while tracing holds the tick phases beside the
device operations.  Export via
``ServingEngine.export_chrome_trace()`` (Chrome/Perfetto JSON),
``GET /debug/trace?rid=<id>`` / ``GET /debug/flightrec`` on the HTTP
front end, and automatic post-mortem dumps into ``EngineHealth`` when
supervision trips.

The observatory (docs/DESIGN.md §5h): ``ServingEngine.cost_report()``
reads XLA's cost/memory analyses off the AOT-compiled decode
executables (per-token FLOPs/bytes, HBM reservation, cache footprint
reconciled against ``kv_reachable_bytes``), ``slo`` tracks declarative
objectives with fast/slow burn-rate alerting (``GET /slo``, folded
into ``health()``), and ``log`` emits structured JSON lines at the
admission/terminal/recovery/shed/restart edges — both planes
module-level no-ops when unconfigured.

Real traffic shapes (docs/DESIGN.md §5i): paged pools take
``prefill_chunk_tokens=`` (bounded chunked prefill interleaved with
decode — a long prompt can no longer blow resident requests'
inter-token p95) and ``prefix_sharing=True`` (refcounted blocks + a
chain-hashed prefix index: admission maps a resident shared prefix
read-only and prefills only the suffix, byte-identical to sharing-off)
— surfaced as ``serving_prefix_hit_rate`` /
``serving_prefix_blocks_shared`` / ``serving_prefill_chunks_total``
and the ``prefix_hit_tokens`` stamp on ``req.admitted`` log lines.

Traffic-grade scheduling (docs/DESIGN.md §5j): requests carry
``priority`` classes (``PRIORITY_CLASSES`` or any int) and optional
``tenant`` fairness keys; admission is (priority, deadline, arrival)-
ordered with per-tenant slot caps, and ``ServingEngine.preempt()``
evicts a decoding victim by spilling its paged K/V (int8 scales
included) to a host-RAM block tier — resumed BYTE-identically with no
new compiles.  ``degrade=True`` closes the loop on the SLO plane: the
multi-window burn alert drives a ladder (preempt low-priority → reduce
spec-K → tighten admission, ``AdmissionTightenedError`` at the door)
that steps down while the alert burns and back up as it clears, every
decision emitted as ``sched.preempt``/``sched.resume``/
``sched.degrade``/``sched.restore`` flight-recorder events and
structured-log lines.  A degraded engine is HEALTHY: ``GET /healthz``
stays 200 and carries the level.

Sharded serving (docs/DESIGN.md §5k): ``ServingEngine(...,
mesh=jit.mesh.DecodeMesh(dp, mp))`` runs the SAME scheduler over a
GSPMD decode pool — the slot axis and paged block pool sharded over
``dp`` (per-shard scratch/free-list partition), attention heads and
MLP hidden over ``mp`` — byte-identical to the unsharded engine with
unchanged compile counts.  The engine sees logical slots only; mesh
engines additionally export ``serving_mesh_devices`` and the per-shard
KV byte gauges (per-chip headroom, not mesh-total optimism).

Crash-durable serving (docs/DESIGN.md §5m): ``journal`` is the
append-only, CRC-framed write-ahead request journal —
``ServingEngine(journal_path=...)`` records admissions (with the
pool's sampling/cache config fingerprint in the header) and per-tick
committed-token batches, ``checkpoint()`` compacts, and
``restore(path)`` lets a FRESH process (or a second engine with the
same weights) adopt the journal plus the ``spill_tier="disk"``
directory and finish every greedy survivor byte-identically with zero
new compiles — torn tails truncate (never crash), fingerprint
mismatches raise typed errors naming both sides, and the RESTORING
state answers ``/healthz`` 503 + Retry-After while deferring (never
dropping) admissions.  ``journal.append``/``spill.write`` are fault
seams; ``health()["last_restore_s"]`` is the restore's wall time
(``tests/test_durable_serving.py``).

Disaggregated serving (docs/DESIGN.md §5n): ``transfer`` is the
versioned K/V hand-off contract — a magic+version+fingerprint-headered,
64-byte-aligned, fsync'd single file (``write_transfer`` /
``TransferReader``) that the disk spill tier, crash restore and tier
hand-off all share — and ``DisaggregatedServing`` runs a prefill-role
engine (admission + chunked prefill, exports at first token over the
``xfer.write`` seam) next to a decode-role engine (adopts via the
PR 15 upload path, never compiles a prefill-chunk executable) behind
one fused-looking front: one stream per request across the hand-off,
byte-identical to the fused engine, deadline shed that includes the
observed mean ``serving_handoff_wait_s``, and
``serving_kv_transfers_total`` / ``serving_kv_transfer_bytes_total``
on the front's registry.  Stale-version files are deleted (resubmit
fallback covers them), alien-fingerprint and pre-upgrade unversioned
files are left alone and logged — never adopted, never crash.

The serving fleet (docs/DESIGN.md §5o): ``ServingFleet`` fronts N
fused engines with the single-engine API — prefix-affinity routing
(the router replays the pool's chain-hash prefix walk against each
engine's epoch-cached ``resident_prefix_digest()`` so shared-prefix
traffic lands where its blocks already live, falling back to
least-loaded placement scored from ``health()`` backpressure), LIVE
request migration (``retire_engine`` preempts victims to their disk
transfer files, ``GenerationPool.detach_spilled`` releases the file
for ``adopt_migration`` on a peer — zero re-prefill, zero new
compiles, prompt+committed resubmit as the always-correct fallback;
engine DEATH replays from the fleet's own forwarded-token record), and
SLO-driven autoscaling (a fleet-level tracker + the §5j dwell/clear
discipline spawning on sustained multiwindow burn and retiring on
sustained clear).  ``FleetSupervisor`` fans per-engine watchdogs in
and escalates unkillable wedges to ``hard_abandon``; the aggregated
``render_prometheus()`` namespaces per-engine series under an
``engine`` label (never double-counting N registries into one scrape)
and adds ``fleet_migrations_total`` /
``fleet_requests_routed_total{reason=affinity|load}``.

Reference parity: the framework-level analog of the reference's
``paddle/fluid/inference/`` serving layer (SURVEY §1), rebuilt
TPU-native over the compiled decode step instead of an executor —
serving-oriented systems work (PAPERS.md, arXiv:2603.09555) treats the
cached decode step as a component inside a request scheduler; this
package is that scheduler.
"""
from . import faults, journal, log, slo, trace, transfer
from .disagg import DisaggregatedServing
from .engine import (PRIORITY_CLASSES, AdmissionTightenedError,
                     DeadlineUnattainableError, QueueFullError,
                     ServingEngine)
from .fleet import ServingFleet
from .journal import (FingerprintMismatchError, JournalCorruptError,
                      JournalWriteError, JournalWriter)
from .http import ServingHTTPFrontend, parse_generate_request
from .log import JsonLinesLogger
from .metrics import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .slo import Objective, SLOTracker
from .stream import RequestState, ResponseStream, StreamStatus
from .supervisor import EngineHealth, FleetSupervisor, Supervisor
from .trace import FlightRecorder, TraceEvent, Tracer
from .transfer import (TransferFingerprintError, TransferFormatError,
                       TransferReader, TransferVersionError,
                       check_fingerprint, write_transfer)

__all__ = [
    "ServingEngine", "QueueFullError", "DeadlineUnattainableError",
    "AdmissionTightenedError", "PRIORITY_CLASSES",
    "ResponseStream", "StreamStatus", "RequestState",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_TIME_BUCKETS",
    "ServingHTTPFrontend", "parse_generate_request",
    "faults", "Supervisor", "EngineHealth", "FleetSupervisor",
    "trace", "Tracer", "FlightRecorder", "TraceEvent",
    "slo", "Objective", "SLOTracker",
    "log", "JsonLinesLogger",
    "journal", "JournalWriter", "JournalWriteError",
    "JournalCorruptError", "FingerprintMismatchError",
    "transfer", "write_transfer", "TransferReader", "check_fingerprint",
    "TransferFormatError", "TransferVersionError",
    "TransferFingerprintError",
    "DisaggregatedServing",
    "ServingFleet",
]
