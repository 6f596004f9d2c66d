"""Per-request sampling as data + batched multi-LoRA
(docs/DESIGN.md §5q).

The contracts pinned here:

1. a MIXED batch — greedy + three sampling configs across three LoRA
   bank rows — emits tokens BYTE-IDENTICAL to dedicated pools each
   serving one config, across seeds, under the exactly-two-compiles
   contract (one executable, any mix: the configs and adapter ids are
   per-slot traced data, never compiled constants);
2. ``cost_version()`` holds still across steady mixed traffic, and a
   ``load_adapter`` hot swap is a bank-row device write — zero new
   compiles, cost fingerprint unmoved, later requests on the row see
   the new fine-tune;
3. a SAMPLED request preempts -> spills to disk -> resumes
   byte-identically (row r draws with ``fold_in(PRNGKey(seed[r]),
   step[r])`` — the stream owes nothing to slot, batch composition,
   or which engine is executing), and the detached PTKV transfer file
   adopts byte-identically on a second pool, sampling config and
   adapter id riding the spill meta;
4. the session fingerprint DROPS the v1 pool-global sampling scalars
   (two pools differing only in default temperature are the same
   executable) and carries the bank GEOMETRY instead; a hand-written
   v1 journal whose fingerprint matches modulo those fields restores
   through the documented upgrade triage (resubmit fallback, logged
   ``journal.upgrade``, deterministic-going-forward), while any other
   mismatch — or a banked engine — still refuses typed;
5. the fleet's adapter registry broadcasts a ``register_adapter`` to
   every active engine AND every later spawn, so adapter traffic is
   byte-identical to a single direct-loaded engine wherever it lands;
6. admission edges refuse typed: an adapter id without a bank row, a
   bankless pool given any nonzero id, a negative temperature, and
   ``unload_adapter`` while a live request is pinned to the row.
"""
import io
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import (InvalidArgumentError,
                                    PreconditionNotMetError)
from paddle_tpu.inference import GenerationPool
from paddle_tpu.models import TransformerLM
from paddle_tpu.nn import lora
from paddle_tpu.serving import ServingEngine, ServingFleet
from paddle_tpu.serving import log as slog
from paddle_tpu.serving.journal import (MAGIC, FingerprintMismatchError,
                                        frame_record)

VOCAB = 128


def _model(seed=0, bank_rows=0, rank=4, load=True):
    pt.seed(seed)
    m = TransformerLM(vocab_size=VOCAB, hidden_size=32, num_layers=1,
                      num_heads=2, intermediate_size=64,
                      max_position=256, causal=True, dropout=0.0)
    if bank_rows:
        lora.attach_lora(m, n_adapters=bank_rows, rank=rank)
        if load:
            for idx in range(1, bank_rows):
                m_w = lora.random_adapter(m, seed=idx, scale=0.5)
                lora.load_adapter(m, idx, m_w)
    return m


def _pool(model, spill=None, slots=4, **over):
    kw = dict(max_len=64, slots=slots, buckets=[32])
    if spill is not None:
        kw.update(cache_layout="paged", block_size=8,
                  spill_tier="disk", spill_dir=str(spill))
    kw.update(over)
    return GenerationPool(model, **kw)


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (n,)).astype("int32") for n in lens]


def _mixed(seed):
    """Greedy + three sampling configs across adapters {0, 1, 2} — the
    batch shape one multi-tenant executable must serve."""
    return [dict(),
            dict(temperature=0.8, seed=seed + 100),
            dict(temperature=1.1, top_k=12, seed=seed + 200, adapter=1),
            dict(temperature=0.6, top_p=0.9, seed=seed + 300,
                 adapter=2)]


# -- 1. mixed batch == dedicated pools, one executable -------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mixed_batch_token_identical_to_dedicated_pools(seed):
    model = _model(bank_rows=4)
    prompts = _prompts(seed, (7, 19, 12, 9))
    configs = _mixed(seed)
    pool = _pool(model)
    for i, (ids, cfg) in enumerate(zip(prompts, configs)):
        pool.submit(ids, 8, request_id="r%d" % i, **cfg)
    mixed = pool.run()
    counts = pool.compile_counts()
    assert counts["prefill"] == 1 and counts["pool_decode"] == 1
    for i, (ids, cfg) in enumerate(zip(prompts, configs)):
        dedicated = _pool(model, slots=1)
        dedicated.submit(ids, 8, request_id="d", **cfg)
        np.testing.assert_array_equal(mixed["r%d" % i],
                                      dedicated.run()["d"])


def test_steady_mixed_traffic_never_moves_cost_version():
    model = _model(bank_rows=4)
    pool = _pool(model)
    prompts = _prompts(3, (7, 19, 12, 9))
    for i, (ids, cfg) in enumerate(zip(prompts, _mixed(3))):
        pool.submit(ids, 8, request_id="w%d" % i, **cfg)
    pool.run()
    counts, cost = pool.compile_counts(), pool.cost_version()
    # a second wave with the configs PERMUTED across the slots: any
    # config-dependence of the executable would surface here
    for i, (ids, cfg) in enumerate(zip(prompts, _mixed(3)[::-1])):
        pool.submit(ids, 8, request_id="x%d" % i, **cfg)
    pool.run()
    assert pool.compile_counts() == counts
    assert pool.cost_version() == cost


# -- 2. hot swap: a device write, never a retrace ------------------------

def test_hot_load_zero_compiles_and_new_weights_serve():
    model = _model(bank_rows=4)
    pool = _pool(model)
    ids = _prompts(0, (11,))[0]
    cfg = dict(temperature=0.9, seed=5, adapter=1)
    rid = pool.submit(ids, 8, **cfg)
    got_before = pool.run()[rid]
    counts, cost = pool.compile_counts(), pool.cost_version()
    pool.load_adapter(1, lora.random_adapter(model, seed=101,
                                             scale=1.0))
    rid = pool.submit(ids, 8, **cfg)
    got_after = pool.run()[rid]
    assert pool.compile_counts() == counts  # the swap compiled NOTHING
    assert pool.cost_version() == cost
    # same prompt, same (seed, step) stream — only the weights moved
    assert np.any(got_before != got_after)


def test_unload_refuses_while_pinned_then_zeroes():
    model = _model(bank_rows=4)
    pool = _pool(model)
    ids = _prompts(1, (9,))[0]
    pool.submit(ids, 8, adapter=2)
    pool.step()
    with pytest.raises(PreconditionNotMetError):
        pool.unload_adapter(2)  # an in-flight request is pinned
    pool.run()
    pool.unload_adapter(2)  # drained: the row zeroes (identity again)
    rid = pool.submit(ids, 8, adapter=2)
    a = pool.run()[rid]
    rid = pool.submit(ids, 8)  # base model
    np.testing.assert_array_equal(a, pool.run()[rid])


# -- 3. sampled spill / resume / migration, byte-identical ---------------

def test_sampled_preempt_spill_resume_byte_identity(tmp_path):
    model = _model(bank_rows=4)
    prompts = _prompts(2, (7, 19, 12))
    subs = [(prompts[0], dict(temperature=1.0, seed=21, adapter=1)),
            (prompts[1], dict()),
            (prompts[2], dict(temperature=0.7, seed=22))]

    undisturbed = _pool(model, spill=tmp_path / "a")
    for i, (ids, cfg) in enumerate(subs):
        undisturbed.submit(ids, 8, request_id="r%d" % i, **cfg)
    want = undisturbed.run()
    counts = undisturbed.compile_counts()

    victimized = _pool(model, spill=tmp_path / "b")
    for i, (ids, cfg) in enumerate(subs):
        victimized.submit(ids, 8, request_id="r%d" % i, **cfg)
    victimized.step()
    victimized.step()
    info = victimized.preempt("r0")  # the SAMPLED adapter-1 request
    assert info["committed_tokens"] > 0
    got = victimized.run()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert victimized.compile_counts() == counts  # resume: no compile
    ss = victimized.spill_stats()
    assert ss["preempts_total"] >= 1 and ss["resumes_total"] >= 1


def test_sampled_adapter_ptkv_migration_byte_identity(tmp_path):
    model = _model(bank_rows=4)
    ids = _prompts(4, (13,))[0]
    cfg = dict(temperature=0.9, seed=31, adapter=2)

    reference = _pool(model, spill=tmp_path / "spill")
    reference.submit(ids, 10, request_id="ref", **cfg)
    want = reference.run()["ref"]

    donor = _pool(model, spill=tmp_path / "spill")
    committed = {}
    donor.on_token = (lambda rid, tok:
                      committed.setdefault(rid, []).append(tok))
    donor.submit(ids, 10, request_id="mig", **cfg)
    donor.step()
    donor.step()
    donor.preempt("mig")
    handoff = donor.detach_spilled("mig")
    assert handoff["rid"] == "mig" and handoff["spill_bytes"] > 0

    # the peer adopts the PTKV file: sampling config + adapter id ride
    # the spill meta, so the resumed rows keep drawing THEIR stream
    # under THEIR fine-tune — no re-prefill, byte-identical
    peer = _pool(model, spill=tmp_path / "spill")
    assert peer.adopt_spill("mig", ids, committed["mig"], 10)
    np.testing.assert_array_equal(peer.run()["mig"], want)
    assert peer.spill_stats()["upload_bytes_total"] > 0


# -- 4. fingerprint + v1 journal upgrade triage --------------------------

def test_fingerprint_drops_global_sampling_carries_bank_geometry():
    base = _model()
    a = _pool(base, temperature=0.0)
    b = _pool(base, temperature=0.9, top_k=7, seed=5)
    fa, fb = a.config_fingerprint(), b.config_fingerprint()
    # two pools differing ONLY in sampling defaults are the SAME
    # executable — the v1 global scalars are gone from the identity
    assert fa == fb
    assert fa["sampling"] == "per-request"
    assert "temperature" not in fa and "sampling_seed" not in fa
    assert fa["lora"] is None
    banked = _pool(_model(bank_rows=4, rank=4))
    fp = banked.config_fingerprint()
    # bank GEOMETRY is compiled (shapes); row contents hot-swap freely
    assert fp["lora"] == {"n_adapters": 4, "rank": 4}
    assert fp != fa


def _engine(model, tmp_path, journal=None, **over):
    kw = dict(max_len=64, slots=2, buckets=[32], cache_layout="paged",
              block_size=8, spill_tier="disk",
              spill_dir=str(tmp_path / "spill"))
    kw.update(over)
    return ServingEngine(model, journal_path=journal, **kw)


def _drain(engine, bound=400):
    n = 0
    while engine.pump(1):
        n += 1
        assert n < bound, "engine failed to drain: wedged"


def _write_v1_journal(path, fp2, ids, max_new, committed):
    """A journal exactly as a v1 engine would have left it: header
    fingerprint carrying the POOL-GLOBAL sampling scalars, admit
    records without ``sampling``/``adapter`` fields."""
    v1 = {k: v for k, v in fp2.items() if k not in ("sampling", "lora")}
    v1.update(temperature=0.7, top_k=5, top_p=0.9, sampling_seed=123)
    body = MAGIC + frame_record({"t": "header", "v": 1,
                                 "fingerprint": v1})
    body += frame_record({"t": "admit", "rid": "old",
                          "ids": [int(t) for t in ids],
                          "max_new": int(max_new), "priority": 0,
                          "tenant": None, "deadline_s": None,
                          "ts": None})
    body += frame_record({"t": "commit",
                          "toks": [["old", committed]]})
    with open(path, "wb") as f:
        f.write(body)
    return v1


def test_journal_v1_upgrade_triage_replays_via_resubmit(tmp_path):
    model = _model()
    probe = _engine(model, tmp_path)
    fp2 = probe._pool.config_fingerprint()
    probe.shutdown(drain=False)
    ids = _prompts(5, (9,))[0]
    jpath = str(tmp_path / "v1.journal")
    _write_v1_journal(jpath, fp2, ids, 8, [3, 7])

    def restore_once():
        eng = _engine(model, tmp_path,
                      journal=str(tmp_path / "fresh.journal"))
        buf = io.StringIO()
        with slog.logging_to(buf):
            summary = eng.restore(jpath)
        streams = {rid: rec.stream for rid, rec in eng._live.items()}
        _drain(eng)
        ups = [json.loads(l) for l in buf.getvalue().splitlines()
               if json.loads(l)["event"] == "journal.upgrade"]
        st = streams["old"].result(timeout_s=0)
        eng.shutdown(drain=False)
        return summary, ups, st

    summary, ups, st = restore_once()
    assert summary["requests_replayed"] == 1
    # the triage is LOGGED, carrying the old global config it applied
    assert ups and ups[0]["temperature"] == 0.7 \
        and ups[0]["seed"] == 123
    assert str(st.state) in ("DONE", "RequestState.DONE")
    # the committed v1 prefix replays into the stream ahead of the
    # freshly decoded tail
    assert list(map(int, st.tokens))[:2] == [3, 7]
    # deterministic-going-forward: a second fresh engine restoring the
    # same v1 journal produces the identical stream (the upgrade
    # contract is determinism via resubmit, not byte-identity with the
    # crashed v1 engine's unrecoverable batch-positional key chain)
    tmp2 = tmp_path / "again"
    tmp2.mkdir()
    _, _, st2 = restore_once()
    assert list(map(int, st2.tokens)) == list(map(int, st.tokens))


def test_journal_v1_any_other_mismatch_still_refuses(tmp_path):
    model = _model()
    probe = _engine(model, tmp_path)
    fp2 = probe._pool.config_fingerprint()
    probe.shutdown(drain=False)
    ids = _prompts(5, (9,))[0]
    jpath = str(tmp_path / "v1bad.journal")
    bad = dict(fp2, max_len=128)  # differs beyond the sampling fields
    _write_v1_journal(jpath, bad, ids, 8, [3])
    eng = _engine(model, tmp_path,
                  journal=str(tmp_path / "fresh.journal"))
    with pytest.raises(FingerprintMismatchError):
        eng.restore(jpath)
    eng.shutdown(drain=False)


def test_journal_v1_refused_on_banked_engine(tmp_path):
    # a v1 writer cannot have journaled adapter ids: the triage only
    # adopts onto a base-model engine, a banked one refuses typed
    bankless = _model()
    probe = _engine(bankless, tmp_path)
    fp2 = probe._pool.config_fingerprint()
    probe.shutdown(drain=False)
    ids = _prompts(5, (9,))[0]
    jpath = str(tmp_path / "v1.journal")
    _write_v1_journal(jpath, fp2, ids, 8, [3])
    banked = _engine(_model(bank_rows=4), tmp_path,
                     journal=str(tmp_path / "fresh.journal"))
    with pytest.raises(FingerprintMismatchError):
        banked.restore(jpath)
    banked.shutdown(drain=False)


# -- 5. fleet adapter registry -------------------------------------------

def test_fleet_register_adapter_broadcasts_and_covers_spawns(tmp_path):
    # bank attached but rows EMPTY: only the fleet registry can make
    # adapter-1 traffic differ from the base model
    model = _model(bank_rows=4, load=False)
    weights = lora.random_adapter(model, seed=7, scale=0.5)
    prompts = _prompts(6, (9, 13, 11, 8, 15, 10))

    reference = _engine(model, tmp_path, slots=4)
    reference.load_adapter(1, weights)
    want = []
    for i, p in enumerate(prompts):
        s = reference.submit(p, 8, request_id="r%d" % i,
                             temperature=0.8, seed=40 + i, adapter=1)
        want.append(s)
    _drain(reference)
    want = [list(map(int, s.status.tokens)) for s in want]
    reference.shutdown(drain=False)

    def factory(engine_id, registry):
        return ServingEngine(model, metrics=registry, max_len=64,
                             slots=2, buckets=[32],
                             cache_layout="paged", block_size=8,
                             spill_tier="disk",
                             spill_dir=str(tmp_path / "fs"))

    fleet = ServingFleet(factory, engines=1)
    fleet.register_adapter(1, weights)
    fleet._spawn_engine("test")  # a LATER spawn inherits the registry
    assert len(fleet._active_handles()) == 2
    streams = [fleet.submit(p, 8, temperature=0.8, seed=40 + i,
                            adapter=1)
               for i, p in enumerate(prompts)]
    while fleet.pump(1):
        pass
    got = [list(map(int, s.status.tokens)) for s in streams]
    # byte-identical WHEREVER the router placed each request: both the
    # broadcast-time engine and the post-registration spawn serve the
    # registered weights
    assert got == want
    fleet.shutdown(drain=False)


# -- 6. admission-edge refusals ------------------------------------------

def test_admission_edge_refusals():
    banked = _pool(_model(bank_rows=4))
    ids = _prompts(0, (7,))[0]
    with pytest.raises(InvalidArgumentError):
        banked.submit(ids, 4, adapter=9)  # no such bank row
    with pytest.raises(InvalidArgumentError):
        banked.submit(ids, 4, adapter=-1)
    with pytest.raises(InvalidArgumentError):
        banked.submit(ids, 4, temperature=-0.5)
    with pytest.raises(InvalidArgumentError):
        banked.submit(ids, 4, temperature=1.0, top_p=0.0)
    bankless = _pool(_model())
    with pytest.raises(InvalidArgumentError):
        bankless.submit(ids, 4, adapter=1)  # no bank at all


# -- 7. a greedy step pays for no draw: one cond, one executable ---------

def _parent_tokens(requests, **pool_kw):
    """The tokens of ``requests`` from a pool whose sampler always takes
    its drawing branch: the branch-free sampler as it stood before the
    draw went behind ``lax.cond``, for every row of every step."""
    import jax

    taken = []

    def always_draw(pred, draw, other):
        taken.append(pred)
        return draw()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, "cond", always_draw)
        pool = _pool(_model(), **pool_kw)
        for rid, (ids, budget, cfg) in requests.items():
            pool.submit(ids, budget, request_id=rid, **cfg)
        out = pool.run()
    # the sampler's own, once in the prefill's trace and once in the
    # step's: no other cond was rerouted
    assert len(taken) == 2
    return {rid: toks.tolist() for rid, toks in out.items()}


def test_drawing_request_among_greedy_one_executable_meta_and_counter():
    """One request that draws joins two greedy ones and leaves: every
    request's tokens are the branch-free sampler's, ``tick.decode``'s
    ``greedy`` reads 1, then 0 while it lives, then 1, the counter
    counts the middle steps, and nothing retraces across the three."""
    p = _prompts(5, (7, 19, 12))
    requests = {"g0": (p[0], 24, {}), "g1": (p[1], 24, {}),
                "d": (p[2], 5, dict(temperature=0.9, top_k=20, seed=7))}
    eng = ServingEngine(_model(), max_len=64, slots=4, buckets=[32])
    tracer = eng.start_trace(capacity=4096)
    try:
        streams = {rid: eng.submit(ids, n, request_id=rid, **cfg)
                   for rid, (ids, n, cfg) in requests.items()
                   if rid != "d"}
        eng.pump(4)
        counts = eng._pool.compile_counts()
        assert counts["prefill"] == 1 and counts["pool_decode"] == 1
        assert eng.metrics.snapshot()[
            "serving_decode_steps_drawing_total"] == 0
        ids, n, cfg = requests["d"]
        streams["d"] = eng.submit(ids, n, request_id="d", **cfg)
        _drain(eng)
    finally:
        eng.stop_trace()
    got = {rid: s.result(timeout_s=0).tokens.tolist()
           for rid, s in streams.items()}
    assert got == _parent_tokens(requests, slots=4)
    assert eng._pool.compile_counts() == counts  # joined, left: no trace
    flags = [e.meta["greedy"] for e in tracer.recorder.snapshot()
             if e.name == "tick.decode"]
    drawing = flags.count(0)
    # the first token is the prefill's: four decode steps hold the row
    assert drawing == 4
    first = flags.index(0)
    assert first >= 4 and flags[first:first + drawing] == [0] * drawing
    assert set(flags[:first]) == {1} == set(flags[first + drawing:])
    assert eng.metrics.snapshot()[
        "serving_decode_steps_drawing_total"] == drawing
    assert eng._pool.steps_drawing == drawing


def test_drawing_row_in_one_shard_of_a_dp_mesh():
    """Under ``DecodeMesh(dp=2)`` the slots are split over two devices
    and only one of them holds a row that draws: the predicate is one value
    for the step, and every row's tokens are the unsharded pool's."""
    from paddle_tpu.jit.mesh import DecodeMesh

    p = _prompts(6, (7, 19, 12, 9))
    cfgs = [dict(temperature=0.8, top_p=0.9, seed=11), {}, {}, {}]

    def run(mesh):
        pool = _pool(_model(), slots=4, mesh=mesh)
        for i, (ids, cfg) in enumerate(zip(p, cfgs)):
            pool.submit(ids, 8, request_id=i, **cfg)
        # slots 0-1 are one shard's rows and 2-3 the other's: the one
        # request that draws leaves a whole shard greedy throughout
        pool.step()
        assert len(pool._active) == 4
        assert len({slot // 2 for slot, st in pool._active.items()
                    if st.sampling.temperature > 0}) == 1
        out = pool.run()
        assert pool.compile_counts()["pool_decode"] == 1
        return [out[i].tolist() for i in range(4)], pool.steps_drawing

    want, steps = run(None)
    got, mesh_steps = run(DecodeMesh(2, 1))
    assert got == want
    assert mesh_steps == steps == 7
