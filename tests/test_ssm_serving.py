"""O(1)-cache model class: recurrent/SSM decoders served by the same
engine (docs/DESIGN.md §5p).

The contracts pinned here:

1. a served ``SSMLM`` (bucketed prefill + per-token decode through
   ``DecodeSession``/``GenerationPool``) emits greedy tokens
   BYTE-IDENTICAL to the eager reference — both the cached per-token
   loop and the full-reforward-from-zero-state loop — across seeds, in
   fp32 (the sequential-scan op-order argument of ``nn/ssm.py``);
2. the exactly-two-compiles contract holds verbatim for the recurrent
   layout: {prefill: 1, decode: 1} per bucket, and preempt/spill/resume
   never adds a compile;
3. preempt → spill → resume is byte-identical through BOTH spill tiers
   (host RAM and disk), and a detached disk spill adopts byte-identical
   on a second engine — the same PTKV transfer contract paged pools
   use, with the recurrent carry as the payload;
4. the fingerprint carries the model class: a transformer engine can
   never adopt a recurrent engine's spill file (or vice versa) — the
   reject is a logged ``xfer.reject`` with ``reason="fingerprint"``,
   never a crash or a silent wrong answer;
5. features that require a POSITIONAL cache (prefix sharing, chunked
   prefill, paged knobs, speculative decoding, the disaggregated
   prefill tier) raise typed construction errors naming the layout;
6. the serving engine's recovery invariants (chaos drain, byte-identity,
   counter reconciliation, zero recompiles) and the SIGKILL journal
   restore hold for the recurrent pool exactly as for paged.

What is not about ``SSMLM``'s own equations runs over the deployed
recurrences too (the ``served`` fixture): ``PowerRetentionLM``, whose
state is a matrix a head, and ``HybridMambaLM``, whose cache holds two
kinds of entry and goes through no file.
"""
import io
import json
import os
import signal
import subprocess
import sys
from typing import NamedTuple

import numpy as np
import pytest
import test_hybrid_mamba as hybrid_toy
import test_power_retention as retention_toy

import paddle_tpu as pt
from paddle_tpu.core.errors import (InvalidArgumentError,
                                    PreconditionNotMetError)
from paddle_tpu.inference import GenerationPool, SpeculativePool
from paddle_tpu.jit.cache import CACHE_LAYOUTS, get_layout
from paddle_tpu.jit.decode import DecodeSession
from paddle_tpu.jit.mesh import DecodeMesh
from paddle_tpu.models import (HybridMambaLM, PowerRetentionLM,
                               TransformerLM)
from paddle_tpu.nn import SSMLM
from paddle_tpu.ops import power_retention
from paddle_tpu.serving import RequestState, ServingEngine, faults
from paddle_tpu.serving import log as slog
from paddle_tpu.serving.faults import FaultPlane


def _ssm(seed=0, **over):
    pt.seed(seed)
    cfg = dict(vocab_size=128, hidden_size=32, num_layers=2, d_state=48,
               dropout=0.0)
    cfg.update(over)
    return SSMLM(**cfg)


def _transformer(seed=0):
    pt.seed(seed)
    return TransformerLM(vocab_size=128, hidden_size=32, num_layers=1,
                         num_heads=2, intermediate_size=64,
                         max_position=256, causal=True, dropout=0.0)


@pytest.fixture(scope="module")
def model():
    return _ssm()


class Served(NamedTuple):
    """A model with what a pool or an engine over it is given and reads."""
    model: object
    kw: dict            # the cache arguments of its pool
    vocab: int
    layout: str         # what ``pool.cache_layout`` reads
    state_bytes: int    # one slot's recurrent state
    d_state: int        # the fingerprint's: the first state field's width
    through_a_file: bool


def _retention():
    cfg = retention_toy.CFG
    pt.seed(0)
    m = PowerRetentionLM(**retention_toy.rw.model_kwargs(cfg))
    m.eval()
    retention_toy.rw.load_into(m, cfg, retention_toy.SEED)
    per_layer = power_retention.state_bytes(
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["head_dim"])
    return Served(m, dict(cache_layout="recurrent"), cfg["vocab_size"],
                  "recurrent", cfg["num_layers"] * per_layer,
                  power_retention.phi_size(cfg["head_dim"]), True)


def _hybrid():
    cfg = hybrid_toy.CFG
    pt.seed(0)
    m = HybridMambaLM(**hybrid_toy.mw.model_kwargs(cfg))
    m.eval()
    hybrid_toy.mw.load_into(m, cfg, hybrid_toy.SEED)
    mamba_layers = m.attention_layers.count(False)
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    return Served(
        m, dict(cache_layout="paged", block_size=8), cfg["vocab_size"],
        "paged+recurrent",
        mamba_layers * (hybrid_toy.SSM_BYTES + hybrid_toy.CONV_BYTES),
        (cfg["mamba_d_conv"] - 1) * inner, False)


_SERVED = {
    "ssm": lambda: Served(_ssm(), dict(cache_layout="recurrent"), 128,
                          "recurrent", 2 * 48 * 4, 48, True),
    "retention": _retention,
    "hybrid": _hybrid,
}


@pytest.fixture(scope="module", params=sorted(_SERVED))
def served(request):
    return _SERVED[request.param]()


def _prompts(seed, lens, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype("int32") for n in lens]


def _eager_cached(model, ids, n):
    """Greedy reference via the eager per-token cache loop: prefill the
    exact (unpadded) prompt, then one forward per token."""
    cache = model.gen_decode_cache(1, len(ids) + n)
    logits, cache = model(ids[None], cache=cache)
    out = [int(np.argmax(np.asarray(logits.value)[0, -1]))]
    while len(out) < n:
        step = np.asarray([[out[-1]]], np.int32)
        logits, cache = model(step, cache=cache)
        out.append(int(np.argmax(np.asarray(logits.value)[0, -1])))
    return np.asarray(out, np.int32)


def _eager_reforward(model, ids, n):
    """Greedy reference with NO cache at all: re-run the full scan from
    zero state over the whole growing sequence each step."""
    seq = list(ids)
    out = []
    for _ in range(n):
        logits = model(np.asarray(seq, np.int32)[None])
        out.append(int(np.argmax(np.asarray(logits.value)[0, -1])))
        seq.append(out[-1])
    return np.asarray(out, np.int32)


# -- byte-identity vs the eager references (fp32) ------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_served_matches_eager_reference(seed):
    model = _ssm(seed)
    sess = DecodeSession(model, max_len=64, buckets=[16, 32],
                         cache_layout="recurrent")
    for ids in _prompts(seed, (5, 11, 20, 7)):
        got = sess.generate(ids[None], 8)
        want = _eager_cached(model, ids, 8)
        np.testing.assert_array_equal(np.ravel(got), want)
        # the recurrence is run as a SEQUENTIAL scan precisely so the
        # padded-bucket prefill, the per-token step and the from-zero
        # re-forward reduce in the same fp32 operation order
        np.testing.assert_array_equal(want,
                                      _eager_reforward(model, ids, 8))


def test_exactly_two_compiles(model):
    sess = DecodeSession(model, max_len=64, buckets=[32],
                         cache_layout="recurrent")
    for ids in _prompts(9, (4, 9, 17, 26)):  # one bucket, many lengths
        sess.generate(ids[None], 6)
    assert sess.compile_counts() == {"prefill": 1, "decode": 1}


def test_pool_matches_session_and_compile_pin(served):
    p = _prompts(3, (5, 9, 7), served.vocab)
    sess = DecodeSession(served.model, max_len=64, buckets=[32],
                         **served.kw)
    want = [np.ravel(sess.generate(ids[None], 8)) for ids in p]
    pool = GenerationPool(served.model, max_len=64, slots=2, buckets=[32],
                          **served.kw)
    got = pool.generate(p, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert pool.compile_counts() == {"prefill": 1, "decode": 0,
                                     "pool_decode": 1, "slot_insert": 1}


# -- preempt / spill / resume --------------------------------------------

@pytest.mark.parametrize("tier", ["host", "disk"])
def test_preempt_spill_resume_byte_identity(model, tier, tmp_path):
    p = _prompts(3, (5, 9, 7))
    kw = {} if tier == "host" else dict(spill_tier="disk",
                                        spill_dir=str(tmp_path))

    def mk():
        return GenerationPool(model, max_len=64, slots=2, buckets=[32],
                              cache_layout="recurrent", **kw)

    ref = mk()
    for i, ids in enumerate(p):
        ref.submit(ids, 8, request_id=i)
    want = ref.run()
    counts = ref.compile_counts()

    pool = mk()
    for i, ids in enumerate(p):
        pool.submit(ids, 8, request_id=i)
    pool.step()
    pool.step()
    assert pool.can_preempt(0)
    info = pool.preempt(0)
    # the spill is the O(1) carry, not blocks: layers × d_state × fp32
    assert info["state_bytes"] == 2 * 48 * 4
    assert info["spill_bytes"] == info["state_bytes"]
    assert info["blocks_spilled"] == 0
    if tier == "disk":
        assert os.listdir(str(tmp_path)), "no transfer file written"
    got = pool.run()
    for i in want:
        np.testing.assert_array_equal(got[i], want[i])
    # resume re-activated through the carry upload, never a recompile
    assert pool.compile_counts() == counts
    if tier == "disk":
        assert not os.listdir(str(tmp_path)), "resume must consume file"
    ss = pool.spill_stats()
    assert ss["enabled"] and ss["preempts_total"] == 1 \
        and ss["resumes_total"] == 1 and ss["spilled_requests"] == 0
    assert ss["spill_bytes_total"] == ss["upload_bytes_total"] \
        == info["state_bytes"]


def _a_cache_of_two_kinds_goes_through_no_file(served, p, tmp_path):
    """The disk tier is refused by name; a victim parked in memory has no
    file to hand over, and a peer adopts nothing: the caller resubmits."""
    def mk(**kw):
        return GenerationPool(served.model, max_len=64, slots=2,
                              buckets=[32], **served.kw, **kw)

    with pytest.raises(InvalidArgumentError,
                       match="spill_tier='disk' writes one kind of cache "
                             "entry.*%s.*has both.*keep spill_tier='host'"
                             % served.layout.replace("+", "\\+")):
        mk(spill_tier="disk", spill_dir=str(tmp_path))
    a = mk()
    for i, ids in enumerate(p):
        a.submit(ids, 8, request_id="r%d" % i)
    a.step()
    a.step()
    a.preempt("r0")
    committed = list(a._spilled["r0"].tokens)
    with pytest.raises(PreconditionNotMetError, match="host tier"):
        a.detach_spilled("r0")
    assert not mk().adopt_spill("r0", p[0], committed, 8)
    assert not os.listdir(str(tmp_path))


def test_detach_and_adopt_cross_engine(served, tmp_path):
    p = _prompts(3, (5, 9, 7), served.vocab)
    if not served.through_a_file:
        return _a_cache_of_two_kinds_goes_through_no_file(served, p,
                                                          tmp_path)

    def mk():
        return GenerationPool(served.model, max_len=64, slots=2,
                              buckets=[32], **served.kw,
                              spill_tier="disk", spill_dir=str(tmp_path))

    ref = mk()
    for i, ids in enumerate(p):
        ref.submit(ids, 8, request_id="r%d" % i)
    want = ref.run()

    a = mk()
    for i, ids in enumerate(p):
        a.submit(ids, 8, request_id="r%d" % i)
    a.step()
    a.step()
    a.preempt("r0")
    committed = list(a._spilled["r0"].tokens)
    handoff = a.detach_spilled("r0")
    assert handoff["spill_bytes"] == served.state_bytes

    b = mk()
    assert b.adopt_spill("r0", p[0], committed, 8)
    for i, ids in enumerate(p[1:], 1):
        b.submit(ids, 8, request_id="r%d" % i)
    got = b.run()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    # the adopted victim resumed via the carry upload, not a re-prefill
    assert b.spill_stats()["upload_bytes_total"] == served.state_bytes


def _parked(pool, p):
    """Park request "v" after three steps; its committed tokens."""
    pool.submit(p, 8, request_id="v")
    for _ in range(3):
        pool.step()
    pool.preempt("v")
    return list(pool._spilled["v"].tokens)


def _try_adopt(pool, p, committed):
    buf = io.StringIO()
    with slog.logging_to(buf):
        ok = pool.adopt_spill("v", p, committed, 8)
    rej = [json.loads(l) for l in buf.getvalue().splitlines()
           if json.loads(l)["event"] == "xfer.reject"]
    return ok, rej


def test_cross_model_class_spill_rejected(served, tmp_path):
    """A transformer engine must never adopt a recurrent engine's spill
    file (and vice versa): the fingerprint carries cache_layout (and
    d_state), so the stale-file triage is an ``xfer.reject`` with
    ``reason="fingerprint"`` — the file is another deployment's
    property, left on disk, and the caller resubmits.  A pool whose
    cache goes through no file looks at none."""
    spill = str(tmp_path)
    p = _prompts(4, (9,), served.vocab)[0]
    tier = dict(spill_tier="disk", spill_dir=spill) \
        if served.through_a_file else {}

    def own(slots=2, **kw):
        return GenerationPool(served.model, max_len=64, slots=slots,
                              buckets=[32], **served.kw, **kw)

    paged = GenerationPool(_transformer(), max_len=64, slots=2,
                           buckets=[32], cache_layout="paged",
                           block_size=8, spill_tier="disk",
                           spill_dir=spill)
    if served.through_a_file:
        rec_pool = own(**tier)
        committed = _parked(rec_pool, p)
        path = rec_pool._spilled["v"].host_path
        assert path is not None and os.path.exists(path)
        ok, rej = _try_adopt(paged, p, committed)
        assert not ok
        assert len(rej) == 1 and rej[0]["reason"] == "fingerprint"
        assert "cache_layout" in rej[0]["keys"]
        # not ours to judge: the recurrent engine's file stays on disk...
        assert os.path.exists(path)
        # ...and the OWNING pool still adopts it byte-identically
        ref = own(slots=1)
        ref.submit(p, 8, request_id="v")
        want = ref.run()["v"]
        fresh = own(**tier)
        assert fresh.adopt_spill("v", p, committed, 8)
        np.testing.assert_array_equal(fresh.run()["v"], want)

    # the mirror direction: a paged spill offered to the served pool (the
    # committed counts may coincide; only the fingerprint matters)
    committed = _parked(paged, p)
    path = paged.detach_spilled("v")["path"]
    ok, rej = _try_adopt(own(**tier), p, committed)
    assert not ok and os.path.exists(path)
    if served.through_a_file:
        assert len(rej) == 1 and rej[0]["reason"] == "fingerprint"
        assert "cache_layout" in rej[0]["keys"]
    else:
        assert rej == []        # no file was opened, so none was judged


# -- typed construction errors -------------------------------------------

def test_layout_registry_typed_errors():
    assert set(CACHE_LAYOUTS) == {"dense", "paged", "recurrent"}
    layout = get_layout("recurrent")
    assert not layout.positional and layout.spillable
    with pytest.raises(InvalidArgumentError, match="recurrent"):
        get_layout("block-sparse")


def test_positional_features_raise_typed_errors(model, tmp_path):
    with pytest.raises(InvalidArgumentError,
                       match="prefix_sharing.*recurrent"):
        GenerationPool(model, max_len=64, slots=2,
                       cache_layout="recurrent", prefix_sharing=True)
    with pytest.raises(InvalidArgumentError,
                       match="prefill_chunk_tokens.*recurrent"):
        GenerationPool(model, max_len=64, slots=2,
                       cache_layout="recurrent", prefill_chunk_tokens=8)
    with pytest.raises(InvalidArgumentError, match="num_blocks"):
        GenerationPool(model, max_len=64, slots=2,
                       cache_layout="recurrent", num_blocks=16)
    with pytest.raises(InvalidArgumentError,
                       match="prefill_only.*recurrent"):
        GenerationPool(model, max_len=64, slots=2,
                       cache_layout="recurrent", prefill_only=True,
                       spill_tier="disk", spill_dir=str(tmp_path))
    with pytest.raises(InvalidArgumentError,
                       match="speculative.*recurrent"):
        SpeculativePool(_transformer(), _transformer(1), max_len=64,
                        cache_layout="recurrent")


def test_model_layout_compatibility_is_checked(model):
    # a transformer has no recurrence carry to serve...
    with pytest.raises(InvalidArgumentError,
                       match="TransformerLM.*recurrent"):
        DecodeSession(_transformer(), max_len=64,
                      cache_layout="recurrent")
    # ...and an SSM has no positional K/V to densify or page
    for layout in ("dense", "paged"):
        with pytest.raises(InvalidArgumentError, match="SSMLM"):
            DecodeSession(model, max_len=64, cache_layout=layout)
    # the carry is the exact decode state: fp32 only
    with pytest.raises(InvalidArgumentError, match="float32"):
        DecodeSession(model, max_len=64, cache_layout="recurrent",
                      cache_dtype="int8")


# -- accounting stamps ---------------------------------------------------

def test_cache_stats_and_fingerprint_stamps(served):
    pool = GenerationPool(served.model, max_len=64, slots=4, buckets=[32],
                          **served.kw)
    stats = pool.cache_stats()
    assert stats["cache_layout"] == pool.cache_layout == served.layout
    assert stats["cache_dtype"] == "float32"
    # the model-class claim, quantified: one slot's recurrent state is a
    # constant of the model's shapes (for ``SSMLM`` layers × d_state × 4
    # bytes), independent of max_len, and with nothing mapped it is all
    # a pool can reach
    assert stats["bytes_per_slot"]["recurrent"] == served.state_bytes
    assert stats["reachable_bytes"] == 4 * served.state_bytes
    fp = pool.config_fingerprint()
    assert fp["cache_layout"] == served.layout
    assert fp["d_state"] == served.d_state
    if served.layout == "recurrent":
        assert stats["d_state"] == served.d_state
        assert stats["pool_bytes"] == stats["reachable_bytes"]
        assert stats["state_bytes_per_slot"] == served.state_bytes
        assert "block_size" not in fp
    else:
        assert stats["pool_bytes"] > stats["reachable_bytes"]
        assert stats["state_bytes_per_slot"] > served.state_bytes
        assert fp["block_size"] == served.kw["block_size"]
    # the positional layouts stamp the SAME per-slot key so capacity
    # comparisons across model classes read one field: a row's K and V
    # at max_len, one layer of width 32 in float32
    paged = GenerationPool(_transformer(), max_len=64, slots=4,
                           buckets=[32], cache_layout="paged",
                           block_size=8)
    assert paged.cache_stats()["state_bytes_per_slot"] == 64 * 2 * 32 * 4


def test_dp2_mesh_identity(served):
    p = _prompts(6, (5, 9, 7, 4), served.vocab)
    plain = GenerationPool(served.model, max_len=64, slots=2, buckets=[32],
                           **served.kw)
    want = plain.generate(p, 6)
    mesh = DecodeMesh(dp=2, mp=1)
    sharded = GenerationPool(served.model, max_len=64, slots=2,
                             buckets=[32], mesh=mesh, **served.kw)
    got = sharded.generate(p, 6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    per_shard = sharded.cache_stats()["per_shard"]
    assert len(per_shard) == 2


# -- serving-engine invariants under chaos -------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_chaos_invariants_hold_for_recurrent(served, seed):
    budgets = (6, 5, 7, 4)
    prompts = _prompts(seed, (5, 9, 7, 4), served.vocab)

    def mk():
        return ServingEngine(served.model, max_len=64, slots=2,
                             buckets=[32], max_retries=8, **served.kw)

    def drive(eng):
        streams = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        iters = 0
        while eng.pump(1):
            iters += 1
            assert iters < 500, "chaos run failed to drain: wedged"
        return streams

    clean = mk()
    clean_streams = drive(clean)
    want = [s.result(timeout_s=0).tokens for s in clean_streams]
    clean_counts = clean.compile_counts()

    eng = mk()
    plane = FaultPlane(chaos_seed=seed, chaos_p=0.08,
                       chaos_points=("pool.step", "stream.deliver"),
                       max_faults=6)
    with faults.injected(plane):
        streams = drive(eng)

    statuses = [s.result(timeout_s=0) for s in streams]
    assert all(st is not None for st in statuses)
    for st, w in zip(statuses, want):
        assert st.state == RequestState.DONE, (seed, st.state, st.error)
        np.testing.assert_array_equal(st.tokens, w)
    assert eng.live_requests == 0 and eng.queue_depth == 0
    assert eng.cache_stats()["cache_layout"] == served.layout
    snap = eng.metrics.snapshot()
    assert snap["serving_requests_submitted_total"] == len(prompts)
    assert snap["serving_requests_completed_total"] == len(prompts)
    assert snap["serving_requests_failed_total"] == 0
    assert snap["serving_tokens_emitted_total"] == \
        sum(st.new_tokens for st in statuses) == sum(len(w) for w in want)
    # recovery is re-allocation, never a recompile
    assert eng.compile_counts() == clean_counts


# -- the SIGKILL journal-restore capstone (slow) -------------------------

_CHILD = r"""
import os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu as pt
from paddle_tpu.nn import SSMLM
from paddle_tpu.serving import ServingEngine

workdir = sys.argv[1]
pt.seed(0)
model = SSMLM(vocab_size=128, hidden_size=32, num_layers=2, d_state=48,
              dropout=0.0)
rng = np.random.RandomState(11)
lens = (5, 9, 7, 4, 6)
prompts = [rng.randint(0, 128, (n,)).astype("int32") for n in lens]
eng = ServingEngine(model, max_len=64, slots=2, buckets=[32, 64],
                    cache_layout="recurrent", spill_tier="disk",
                    spill_dir=os.path.join(workdir, "spill"),
                    journal_path=os.path.join(workdir, "wal.journal"))
for i, p in enumerate(prompts[:2]):
    eng.submit(p, 8, request_id="low%d" % i, priority="low")
eng.pump(2)
for i, p in enumerate(prompts[2:]):
    eng.submit(p, 12, request_id="high%d" % i, priority="high")
eng.preempt()   # park a low victim's carry in the disk tier
eng.pump(2)
parked = sum(1 for r in eng._live.values() if r.state == "PREEMPTED")
sys.stdout.write("LIVE %d PARKED %d\n" % (eng.live_requests, parked))
sys.stdout.flush()
# the actual crash: SIGKILL, mid-decode — no drain, no flush, no exit
# handlers; everything the restore needs is already on disk
os.kill(os.getpid(), signal.SIGKILL)
"""


@pytest.mark.slow  # fresh interpreter + compile in the child
def test_subprocess_crash_restore_byte_identical(tmp_path):
    """Engine A (separate PROCESS, recurrent pool) admits mixed-priority
    traffic with a disk-spilled victim and is SIGKILL'd mid-decode;
    engine B restores from the journal + spill dir and finishes every
    greedy survivor byte-identically — the §5m durability contract held
    by the O(1) carry exactly as by paged K/V."""
    workdir = str(tmp_path)
    child = os.path.join(workdir, "crash_child.py")
    with open(child, "w") as f:
        f.write(_CHILD)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, child, workdir],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=repo)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                proc.stderr[-1500:])
    assert "PARKED 1" in proc.stdout, proc.stdout

    model = _ssm()
    rng = np.random.RandomState(11)
    lens = (5, 9, 7, 4, 6)
    prompts = [rng.randint(0, 128, (n,)).astype("int32") for n in lens]

    def mk(journal=None):
        return ServingEngine(model, max_len=64, slots=2,
                             buckets=[32, 64], cache_layout="recurrent",
                             spill_tier="disk",
                             spill_dir=os.path.join(workdir, "spill"),
                             journal_path=journal)

    def drain(engine, bound=400):
        n = 0
        while engine.pump(1):
            n += 1
            assert n < bound, "engine failed to drain: wedged"

    ref = mk()
    for warm_len in (20, 50):
        ref.submit(rng.randint(0, 128, (warm_len,)).astype("int32"), 2)
        drain(ref)
    streams = [ref.submit(p, 8, request_id="low%d" % i, priority="low")
               for i, p in enumerate(prompts[:2])]
    ref.pump(2)
    streams += [ref.submit(p, 12, request_id="high%d" % i,
                           priority="high")
                for i, p in enumerate(prompts[2:])]
    drain(ref)
    want = {s.request_id: s.result(timeout_s=0).tokens for s in streams}
    clean_counts = ref.compile_counts()

    jpath = os.path.join(workdir, "wal.journal")
    eng_b = mk(journal=jpath)
    for warm_len in (20, 50):
        eng_b.submit(rng.randint(0, 128, (warm_len,)).astype("int32"), 2)
        drain(eng_b)
    counts_before = eng_b.compile_counts()
    summary = eng_b.restore(jpath)
    assert summary["requests_replayed"] == 5
    assert summary["adopted_from_spill"] == 1
    restored = {rid: rec.stream for rid, rec in eng_b._live.items()}
    drain(eng_b)
    for rid, s in restored.items():
        st = s.result(timeout_s=0)
        assert st.state == "DONE"
        np.testing.assert_array_equal(np.asarray(st.tokens), want[rid])
    assert eng_b.compile_counts() == counts_before == clean_counts
    # the adopted victim resumed via the carry upload, not a re-prefill
    assert eng_b.spill_stats()["upload_bytes_total"] == 2 * 48 * 4
