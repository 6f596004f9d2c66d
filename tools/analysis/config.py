"""Analysis configuration: walk roots, hot-path roots, dynamic edges.

Everything here is DATA the engine/rules consume, so the policy (what
counts as the decode hot path, which dynamic dispatch points exist) is
reviewable in one place instead of buried in rule code.
"""

# Trees the engine walks, relative to the repo root.  Missing entries
# are skipped (fixture trees in tests pass a bare tmp directory, which
# falls back to "every .py under root").
WALK_ROOTS = ("paddle_tpu", "tools", "tests")

# Directories never walked (caches, VCS).
SKIP_DIRS = {".git", "__pycache__", ".jax_cache", ".pytest_cache"}

# -- hot-path roots (rule: host-sync-in-hot-path) ------------------------
# Functions whose transitive callees form the decode hot path: the
# compiled step fns of DecodeSession, the pool/engine tick, and the
# host-driven decode loops.  Matched against qualname suffixes
# ("Class.method" or bare function name).
HOT_ROOTS = (
    "DecodeSession._prefill",
    "DecodeSession._decode",
    "GenerationPool.step",
    "ServingEngine._tick",
    # host-driven seq2seq decode loop (nn/decode.py): eager by design,
    # but its per-step body is hot all the same
    "dynamic_decode",
)

# -- dynamic-dispatch edges the AST cannot resolve -----------------------
# caller qualname suffix -> callee qualname suffixes.  These annotate
# the dynamic seams of the decode path: the session's model indirection
# (self._model(...)), container iteration over LayerList, the pool's
# serving-layer lifecycle hooks, and the fault-injection plane (the
# pool's `_fire` helper lazily binds serving.faults, and
# `faults.fire` dispatches to the installed FaultPlane — both invisible
# to the AST).  Keeping them explicit is the deal static analysis makes
# with dynamic dispatch — a new seam needs a new line here, which
# review can see.
EXTRA_EDGES = {
    # (collective_quant is the §5r seam install: the decode body runs
    # under the contextmanager, so its region is part of the hot path)
    "DecodeSession._run_model": ("TransformerLM.forward",
                                 "SSMLM.forward",
                                 "collective_quant"),
    # O(1)-cache model class (docs §5p): the CacheLayout protocol's
    # traced hooks dispatch through a layout object chosen at
    # construction (an attribute call the AST cannot resolve), and the
    # SSM forward fans into its recurrence blocks — declared so the
    # session/pool prefill and step paths stay hot-path-audited for
    # every registered layout
    "DecodeSession._prefill": ("CacheLayout.begin_prefill",
                               "CacheLayout.finalize_prefill",
                               "RecurrentLayout.begin_prefill",
                               "RecurrentLayout.finalize_prefill"),
    "GenerationPool._insert": ("DenseLayout.insert_row",
                               "PagedLayout.insert_row",
                               "RecurrentLayout.insert_row"),
    "GenerationPool._pool_decode": ("CacheLayout.freeze_step",
                                    "RecurrentLayout.freeze_step"),
    "SSMLM.forward": ("GatedSSMBlock.forward",),
    # fused pallas decode kernel (docs §5l): the ops-layer routing seam
    # dispatches to the pallas entry points behind function-local
    # imports (invisible to the AST), and both kernels sit on the
    # decode hot path through the traced decode-cache forwards — the
    # whole route (gate -> kernel wrapper -> pallas_call) is declared
    # so the hot-path rules audit it like every other dynamic seam
    "decode_attention": ("decode_attention_kernel",),
    "paged_decode_attention": ("paged_decode_attention_kernel",),
    "TransformerEncoder.forward": ("TransformerEncoderLayer.forward",),
    "TransformerDecoder.forward": ("TransformerDecoderLayer.forward",),
    # the one tick (docs "The tick"): ``step`` is GenerationPool's for
    # every kind of pool and reaches a kind's step through hooks the
    # AST resolves to the base class only, so the speculative round's
    # and the block pool's overrides are declared: the launch-side
    # cursor walk (``_control``) and the deliver loop are hot like the
    # plain pool's.  The skeleton reaches the hooks through
    # ``_launch_step`` and ``_settle_one``, direct self-calls
    "GenerationPool.step": ("ServingEngine._on_tokens",
                            "ServingEngine._on_finish",
                            "ServingEngine._on_prefill_done"),
    "GenerationPool._launch_step": ("SpeculativePool._sync_step_inputs",
                                    "SpeculativePool._launch",
                                    "SpeculativePool._decode_meta",
                                    "BlockDiffusionPool._launchable",
                                    "BlockDiffusionPool._sync_step_inputs",
                                    "BlockDiffusionPool._decode_meta",
                                    "BlockDiffusionPool._launch"),
    "GenerationPool._settle_one": ("SpeculativePool._deliver",
                                   "BlockDiffusionPool._deliver"),
    "GenerationPool._commit": ("BlockDiffusionPool._leaving",),
    # the download's tokens leave in one call of the batch hook (the
    # engine's, or the per-token adapter a pool starts with), then the
    # rows that ended are finished
    "GenerationPool._hand_on": ("ServingEngine._on_tokens",
                                "GenerationPool._each_token",
                                "BlockDiffusionPool._finish"),
    "GenerationPool._refill": ("ServingEngine._on_admit",
                               "ServingEngine._on_resume",
                               "GenerationPool._resume",
                               "BlockDiffusionPool._prefill_row",
                               "BlockDiffusionPool._start_slot"),
    # prefix-sharing admission + chunked prefill (docs §5i): the
    # admission match and the chunk dispatch are new hot-path seams —
    # the admission write and the chunk executable dispatch through
    # AotFunction wrappers (invisible attribute calls), activation fans
    # into the serving hooks and the speculative pool's draft-twin
    # prefill, so the whole path stays hot-path-audited
    "GenerationPool._admit_chunked": ("AotFunction.__call__",
                                      "ServingEngine._on_admit"),
    "GenerationPool._chunk_work": ("AotFunction.__call__",),
    "GenerationPool._activate": ("SpeculativePool._on_activated",),
    "GenerationPool._commit_first": ("ServingEngine._on_prefill_done",),
    # traffic-grade scheduling (docs §5j): the degradation ladder's
    # preempt decision dispatches into the pool's spill path (victim
    # K/V → host pool, the one deliberate spill-boundary device_get),
    # and the refill's resume re-pages blocks in and re-activates the
    # slot — the serving-layer on_resume hook and the speculative
    # pool's draft re-prefill are attribute-assigned/overridden seams
    # the AST cannot see, so the whole ladder→preempt→spill and
    # resume→page-in→re-activate chain is declared hot and audited
    "ServingEngine._degrade_eval": ("ServingEngine._preempt_for_priority",
                                    "SLOTracker.alerting_names"),
    "ServingEngine._preempt_for_priority": ("ServingEngine._do_preempt",),
    "ServingEngine._do_preempt": ("GenerationPool.preempt",),
    "GenerationPool.preempt": ("SpeculativePool._preempt_guard",),
    "GenerationPool._resume": ("ServingEngine._on_resume",
                               "SpeculativePool._on_resumed",
                               "GenerationPool._reclaim_one_spilled"),
    # sharded serving (docs §5k): the mesh placement helpers are
    # reached through ``self._mesh`` — assigned from a constructor
    # ARGUMENT, so the AST's local-constructor type inference cannot
    # see DecodeMesh behind it.  Declaring the seams keeps the
    # step-input re-placement (fires where a launch takes other rows
    # than the last), the shard-mapped admission chain (_choose_shard →
    # per-shard prefix match), and the cache re-placement inside
    # recovery/reset hot-path-audited like every other dynamic seam
    # (the _refill → _choose_shard → per-shard match chain is direct
    # self-calls the AST already resolves — no edge needed there)
    "GenerationPool._place": ("DecodeMesh.place",),
    "GenerationPool._new_cache": ("DecodeMesh.place_cache",),
    "SpeculativePool._new_draft_cache": ("DecodeMesh.place_cache",),
    "DecodeMesh.place_cache": ("DecodeMesh.place",),
    "DecodeMesh.place": ("DecodeMesh.sharding",),
    # quantized mp collectives (docs §5r): the transformer's two
    # row-parallel call sites gate on the thread-local seam (active()
    # returns a context installed by the session's _collective_seam —
    # pure dynamic state the AST cannot follow), row_parallel_linear's
    # shard_map body closes over qpsum, and qpsum's quantize/dequantize
    # run under jax.vmap wrappers (lambda indirection) — the whole
    # seam→shard_map→qpsum→(de)quantize chain is declared so the
    # decode hot path stays audited through the quantized collectives
    "TransformerEncoderLayer.forward": ("_row_parallel_seam",),
    "MultiHeadAttention.forward": ("_row_parallel_seam",),
    "_row_parallel_seam": ("row_parallel_linear",),
    "row_parallel_linear": ("qpsum", "psum_wire_bytes",
                            "qpsum_wire_bytes"),
    "qpsum": ("quantize_int8", "dequantize_int8"),
    "qall_gather": ("quantize_int8", "dequantize_int8"),
    # crash-durability plane (docs §5m): the journal handle is a
    # conditional constructor assignment (`None if ... else
    # JournalWriter(...)`) the local-constructor inference cannot see
    # through, and the writer fires the fault seam via a module
    # attribute call — declaring the engine→journal.append→fsync chain
    # keeps the per-tick WAL flush hot-path-audited like every other
    # plane.  restore() reaches the pool's adoption/resubmit machinery
    # behind self._pool (the same dynamic seam as _recover's), so the
    # restore→replay→submit chain is declared too: a restore is cold
    # by definition, but its callees (submit, adopt_spill) are shared
    # with hot paths and must be audited under both reachabilities.
    "ServingEngine._journal_append": ("JournalWriter.append",),
    "ServingEngine._journal_flush": ("JournalWriter.sync",),
    "JournalWriter.append": ("fire",),
    "ServingEngine._resubmit_record": ("GenerationPool.submit",),
    "ServingEngine.restore": ("read_journal", "replay",
                              "GenerationPool.adopt_spill",
                              "ServingEngine._resubmit_record",
                              "ServingEngine.checkpoint"),
    "ServingEngine.checkpoint": ("JournalWriter.compact",),
    # disaggregated serving (docs §5n): the transfer contract is reached
    # behind a lazy module import (`_transfer_mod()` — invisible to the
    # AST) from the pool's spill write/read/adopt paths, the prefill
    # tier's export sweep fires the attribute-assigned on_handoff hook
    # into the front's bridge, and the front drives both tier engines
    # through constructor-built attributes — the whole
    # park→export→transfer-write→adopt hand-off chain is declared so
    # the hot-path rules audit it like the spill tier it generalizes
    "GenerationPool._spill_write": ("write_transfer",),
    "GenerationPool._spill_read": ("TransferReader.__init__",),
    "GenerationPool.adopt_spill": ("TransferReader.__init__",
                                   "check_fingerprint"),
    "write_transfer": ("fire",),
    "ServingEngine._export_sweep": ("GenerationPool.export_kv",
                                    "GenerationPool.cancel",
                                    "DisaggregatedServing._on_handoff"),
    "ServingEngine._adopt_live": ("GenerationPool.adopt_spill",),
    "DisaggregatedServing._bridge": ("ServingEngine.adopt_transfer",),
    # serving fleet (docs §5o): the router, migration and autoscale
    # paths all reach member engines behind ``_EngineHandle.engine``
    # attributes (plain object slots — invisible to the AST's
    # local-constructor inference), and the fleet supervisor reaches
    # the fleet behind a constructor ARGUMENT.  Declaring the seams
    # keeps the route→submit fan-out, the digest refresh the router
    # hashes against (engine → pool behind self._pool), the
    # drain→checkpoint→migrate_out→adopt_migration hand-off chain and
    # the watchdog escalation hot-path-audited like the single-engine
    # planes they compose
    "ServingFleet.submit": ("ServingEngine.submit",),
    "ServingFleet._refresh_digest":
        ("ServingEngine.resident_prefix_digest",),
    "ServingEngine.resident_prefix_digest":
        ("GenerationPool.prefix_digest",),
    "ServingFleet.pump": ("ServingEngine.pump",),
    "ServingFleet.retire_engine": ("ServingEngine.checkpoint",
                                   "ServingEngine.shutdown"),
    "ServingFleet._migrate_record": ("ServingEngine.migrate_out",),
    "ServingFleet._adopt_onto": ("ServingEngine.adopt_migration",),
    "ServingEngine.migrate_out": ("GenerationPool.detach_spilled",
                                  "GenerationPool.cancel"),
    "FleetSupervisor.check_once": ("Supervisor.check_once",
                                   "ServingFleet.hard_abandon"),
    # fault plane: the hot path's module-level no-op check fans into the
    # installed plane, so the plane's own fire() is hot-path-audited
    "_fire": ("fire",),
    "fire": ("FaultPlane.fire",),
    "ResponseStream._put_token": ("fire",),
    "ServingEngine._on_tokens": ("ResponseStream._put_token",
                                 "SLOTracker.observe_latencies"),
    # trace plane (serving/trace.py): the hot path's module-level no-op
    # check (`trace.instant` / `_trace_active()`) fans into the
    # installed Tracer; span context managers (`with tr.span(...)`) and
    # the recorder append behind them are invisible to the AST, so the
    # whole emission path is declared here and hot-path-audited like
    # the fault plane's
    "instant": ("Tracer.instant",),
    "ServingEngine._run_tick_traced": ("Tracer.span", "Tracer.instant"),
    # every phase of a tick, the pool's and the engine's, is spanned
    # through this one helper
    "tick_phase": ("Tracer.span",),
    "ServingEngine.submit": ("Tracer.span",),
    "Tracer.span": ("_Span.__enter__", "_Span.__exit__", "_Span.set"),
    "_Span.__exit__": ("Tracer._emit",),
    "Tracer.instant": ("Tracer._emit",),
    "Tracer._emit": ("FlightRecorder.append",),
    # the fault plane reports every injection into the trace plane
    "FaultPlane.fire": ("instant",),
    # AOT compile-and-call wrapper (jit/aot.py): the pool/session jit
    # attributes resolve to their traced bodies via the jit bindings,
    # but the WRAPPER's dispatch (key lookup + compiled call) sits on
    # the same hot path and is declared here so the host-sync rule
    # audits it; the compile-miss path runs once per executable, never
    # in steady state, but is reachable and therefore audited too
    "GenerationPool._launch": ("AotFunction.__call__",),
    "SpeculativePool._launch": ("AotFunction.__call__",),
    "BlockDiffusionPool._launch": ("AotFunction.__call__",),
    "BlockDiffusionPool._prefill_row": ("AotFunction.__call__",),
    "AotFunction.__call__": ("AotFunction._compile_miss",),
    "AotFunction._compile_miss": ("analyze_compiled", "kv_arg_bytes"),
    # SLO plane (serving/slo.py): fed from the engine's tick path
    # behind is-None guards; the tracker's own emission (alert flips
    # into the trace + structured log) is declared so the whole seam
    # is hot-path-audited like the fault/trace planes
    "ServingEngine._close_tick": ("SLOTracker.note_tick",),
    "SLOTracker.note_tick": ("_ObjectiveState.roll", "instant",
                             "emit"),
    # structured-log plane (serving/log.py): module-level `emit` is
    # the is-None seam; the installed logger's emit is behind it
    "emit": ("JsonLinesLogger.emit",),
    "ServingEngine._finalize": ("ResponseStream._finalize",
                                "SLOTracker.observe_terminal",
                                "emit"),
    # recovery: the engine rebuilds whichever pool variant it owns and
    # resubmits through the pool's host API — all behind self._pool
    "ServingEngine._recover": ("GenerationPool.reset",
                               "SpeculativePool.reset",
                               "GenerationPool.submit"),
    "dynamic_decode": ("BeamSearchDecoder.initialize",
                       "BeamSearchDecoder.step",
                       "BeamSearchDecoder.finalize"),
}

# -- host-sync markers (rule: host-sync-in-hot-path) ---------------------
# numpy-module functions that materialize their argument on host.
NP_SYNC_FUNCS = {"asarray", "array", "stack", "concatenate"}
# jax-module functions that block / transfer.
JAX_SYNC_FUNCS = {"device_get", "block_until_ready"}
# builtins that force a traced value to host when applied to device math
# (only flagged when the argument contains a jax/jnp call — shape ints
# and python config scalars stay quiet).
BUILTIN_SYNC_FUNCS = {"float", "int", "bool"}
# attribute calls that always materialize.
ATTR_SYNC_CALLS = {"item", "tolist"}

# -- lock discipline (rule: lock-discipline) -----------------------------
# Mutating method names that count as a write to ``self.X`` when called
# as ``self.X.<name>(...)``.  Deliberately excludes ``set`` (Gauge.set /
# Event.set are thread-safe by design) and queue put/get.
MUTATOR_METHODS = {
    "pop", "popleft", "append", "appendleft", "extend", "add", "remove",
    "discard", "clear", "insert", "update", "setdefault",
}

# -- timing (rule: unblocked-timing) -------------------------------------
# Calls considered benign inside a timed span (pure host work).
BENIGN_SPAN_CALLS = {
    "append", "extend", "len", "print", "range", "zip", "min", "max",
    "sorted", "sum", "join", "split", "format", "get", "items", "keys",
    "values", "perf_counter", "time", "monotonic", "round", "abs",
    "list", "tuple", "dict", "set", "str", "repr", "enumerate",
}
# In-span calls that make a timing span honest (explicit sync).
SPAN_SYNC_CALLS = {"block_until_ready", "device_get", "asarray", "array",
                   "item", "float", "int", "tolist"}
