"""The host one step behind the device (docs/DESIGN.md 5t), at widths the
CPU holds.

A pool whose carry lives on the device (the plain and the block pool)
launches step t+1 before it has downloaded step t.  What that may not
change: every request's tokens and finish reason.  The reference is the
SYNCHRONOUS schedule of the same hooks (``_depth = 0``: launch, download
and deliver in turn, the order every pool had before), so the two differ
in nothing but the order of host and device work.
"""
import contextlib
import os
import sys
import types

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.inference import (BlockDiffusionPool,  # noqa: E402
                                  GenerationPool, SpeculativePool)
from paddle_tpu.inference import block_diffusion as bd_mod  # noqa: E402
from paddle_tpu.inference import generation as gen_mod  # noqa: E402
from paddle_tpu.inference import speculative as spec_mod  # noqa: E402
from paddle_tpu.models import BlockDiffusionMoELM, TransformerLM  # noqa
from paddle_tpu.serving import trace  # noqa: E402
from paddle_tpu.serving.trace import Tracer  # noqa: E402


@pytest.fixture(scope="module")
def lm():
    pt.seed(5)
    m = TransformerLM(vocab_size=96, hidden_size=32, num_layers=2,
                      num_heads=2, intermediate_size=64, max_position=64,
                      causal=True, dropout=0.0)
    m.eval()
    return m


@pytest.fixture(scope="module")
def block_lm():
    pt.seed(3)
    m = BlockDiffusionMoELM(
        vocab_size=96, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, head_dim=16, expert_size=16, num_experts=4,
        top_k=2, block_length=4, mask_token_id=95, denoise_steps=2,
        dtype="float32")
    m.eval()
    return m


class SyncPool(GenerationPool):
    _depth = 0


class SyncBlockPool(BlockDiffusionPool):
    _depth = 0


# slots x blocks so tight that a request admitted where another ended
# MUST take the blocks that one freed: 2 slots, 6 blocks of 8 + scratch,
# every request reserving 3
KINDS = {
    "plain-dense": (GenerationPool, SyncPool, "lm", dict()),
    "plain-paged": (GenerationPool, SyncPool, "lm", dict(
        cache_layout="paged", block_size=8, num_blocks=7)),
    "plain-chunked": (GenerationPool, SyncPool, "lm", dict(
        cache_layout="paged", block_size=8, num_blocks=7,
        prefill_chunk_tokens=8)),
    "block-dense": (BlockDiffusionPool, SyncBlockPool, "block_lm", dict(
        cache_dtype="float32")),
    "block-paged": (BlockDiffusionPool, SyncBlockPool, "block_lm", dict(
        cache_dtype="float32", cache_layout="paged", block_size=8,
        num_blocks=7)),
}
PROMPTS = (5, 9, 7, 11, 6, 10)
BUDGETS = (5, 12, 8, 4, 12, 1)


def prompts():
    rng = np.random.RandomState(31)
    return [rng.randint(0, 90, (n,)).astype("int32") for n in PROMPTS]


def build(request, kind, sync=False, **more):
    ahead, level, model, kw = KINDS[kind]
    return (level if sync else ahead)(
        request.getfixturevalue(model), max_len=24, slots=2,
        buckets=[16], **dict(kw, **more))


def drain(pool, work=None):
    """Every request through the pool; ({rid: tokens}, {rid: reason},
    admissions as (rid, steps in flight, the slot's blocks))."""
    reasons, admits = {}, []
    pool.on_finish = lambda rid, toks, why: reasons.__setitem__(rid, why)
    pool.on_admit = lambda rid, slot, n: admits.append(
        (rid, len(pool._flights),
         tuple(getattr(pool, "_slot_blocks", {}).get(slot, ()))))
    for i, (p, n) in enumerate(work or zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    out = {rid: t.tolist() for rid, t in pool.run().items()}
    return out, reasons, admits


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_tokens_and_reasons_are_the_synchronous_schedules(request, kind):
    # no EOS yet: the streams from which a mid-stream EOS is chosen
    free, why, _ = drain(build(request, kind, sync=True))
    assert set(why.values()) == {"length"}
    assert [len(free[i]) for i in range(6)] == list(BUDGETS)
    # the token request 1 emits third ends it (and whoever else emits
    # it) at that token: an end the host sees a step late
    eos = free[1][2]
    want, want_why, _ = drain(build(request, kind, sync=True, eos_id=eos))
    assert want_why[1] == "eos" and len(want[1]) <= 3
    assert "length" in want_why.values()
    pool = build(request, kind, eos_id=eos)
    with trace.tracing(Tracer(capacity=8192)) as tracer:
        got, got_why, admits = drain(pool)
    assert got == want and got_why == want_why
    delivers = [e.meta for e in tracer.recorder.snapshot()
                if e.name == "tick.deliver"]
    decodes = [e.meta for e in tracer.recorder.snapshot()
               if e.name == "tick.decode"]
    # the mechanism engaged: launches made with a step in flight, a row
    # that had ended when its step was launched, and an admission under
    # the step in flight
    assert sum(m["ahead"] for m in decodes) > len(decodes) // 2
    assert sum(m["ended"] for m in delivers) >= 1
    assert all(m["ended"] <= m["rows"] for m in delivers)
    assert any(flights for _, flights, _ in admits)
    assert not pool._flights and not pool._firsts and not pool._active
    if "paged" in kind or "chunked" in kind:
        # ... into the very blocks the slot's last owner freed the tick
        # before, while the step that still wrote to them was in flight:
        # the splice is dispatched behind it and the device runs in
        # order (5t, "a row that ended")
        held = {rid: set(blocks) for rid, _, blocks in admits}
        assert any(flights and held[rid] & held[1]
                   for rid, flights, _ in admits if rid != 1), admits
        assert len(pool._free_blocks) == 6


def test_speculative_pool_stays_level_with_the_device(request, lm):
    assert SpeculativePool._depth == 0 and GenerationPool._depth == 1
    assert BlockDiffusionPool._depth == 1
    kw = dict(max_len=24, slots=2, buckets=[16], cache_layout="paged",
              block_size=8, num_blocks=9)
    want, want_why, _ = drain(SyncPool(lm, **kw))
    pool = SpeculativePool(lm, lm, spec_k=2, **kw)
    got, got_why, admits = drain(pool)
    assert got == want and got_why == want_why
    assert not any(flights for _, flights, _ in admits)


# -- out-of-tick calls settle the step in flight -----------------------------

def steps_until_in_flight(pool, n=3):
    for _ in range(n):
        pool.step()
    assert pool._flights, "nothing in flight: the case is not the case"


@pytest.mark.parametrize("kind", ["plain-dense", "plain-paged",
                                  "block-paged"])
def test_cancel_with_a_step_in_flight(request, kind):
    want, _, _ = drain(build(request, kind, sync=True))
    pool = build(request, kind)
    for i, (p, n) in enumerate(zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    steps_until_in_flight(pool)
    seen = []
    pool.on_token = lambda rid, tok: seen.append(rid)
    assert pool.cancel(1) == "active"
    # level with the device first: what was in flight was delivered
    # (to the others as to the victim), then the slot went
    assert not pool._flights and not pool._firsts and seen
    assert 1 not in {st.rid for st in pool._active.values()}
    pool.on_token = None
    got = {rid: t.tolist() for rid, t in pool.run().items()}
    assert got == {rid: t for rid, t in want.items() if rid != 1}
    if "paged" in kind:
        assert len(pool._free_blocks) == 6


@pytest.mark.parametrize("layout", ["paged", "recurrent"])
def test_preempt_with_a_step_in_flight(request, lm, layout):
    from paddle_tpu import nn

    if layout == "recurrent":
        pt.seed(2)
        model = nn.SSMLM(vocab_size=96, hidden_size=32, num_layers=2,
                         d_state=16, dropout=0.0)
        model.eval()
        kw = dict(cache_layout="recurrent")
    else:
        model = lm
        kw = dict(cache_layout="paged", block_size=8, num_blocks=7)
    kw.update(max_len=24, slots=2, buckets=[16])
    want, _, _ = drain(SyncPool(model, **kw))
    pool = GenerationPool(model, **kw)
    for i, (p, n) in enumerate(zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    steps_until_in_flight(pool)
    assert pool.can_preempt(1) and not pool._flights
    pool.step()
    assert pool._flights
    info = pool.preempt(1)
    # the spill is of the K/V as the delivered tokens left it
    victim = pool._spilled[1]
    assert info["committed_tokens"] == len(victim.tokens) >= 2
    assert victim.tokens == want[1][:len(victim.tokens)]
    got = {rid: t.tolist() for rid, t in pool.run().items()}
    assert got == want


def test_export_kv_with_a_first_token_on_the_device(request, lm, tmp_path):
    kw = dict(max_len=24, slots=2, buckets=[16], cache_layout="paged",
              block_size=8, num_blocks=7, spill_tier="disk")
    want, _, _ = drain(SyncPool(lm, **dict(kw, spill_tier="host")))
    tier = GenerationPool(lm, prefill_only=True,
                          spill_dir=str(tmp_path / "a"), **kw)
    decode = GenerationPool(lm, spill_dir=str(tmp_path / "a"), **kw)
    done = []
    tier.on_prefill_done = done.append
    p, n = prompts()[1], BUDGETS[1]
    tier.submit(p, n, request_id=1)
    # the prefill is dispatched and its first token is still on the
    # device: the export brings it home before it reads the slot
    tier._admit_phase(None)
    assert tier._firsts and not done
    with pytest.raises(Exception):
        tier.export_kv("nobody")
    assert done == [1] and not tier._firsts
    info = tier.export_kv(1)
    assert info["committed_tokens"] == 1
    assert decode.adopt_spill(1, p, want[1][:1], n)
    assert decode.run()[1].tolist() == want[1]


def test_weight_swap_and_reset_with_a_step_in_flight(request, lm):
    pool = build(request, "plain-paged")
    want, _, _ = drain(build(request, "plain-paged", sync=True))
    for i, (p, n) in enumerate(zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    steps_until_in_flight(pool)
    pool.refresh_weights()
    assert not pool._flights
    got = {rid: t.tolist() for rid, t in pool.run().items()}
    assert got == want
    # reset drops what is in flight: never awaited, never delivered
    for i, (p, n) in enumerate(zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    steps_until_in_flight(pool)
    pool.on_token = lambda rid, tok: pytest.fail("delivered after reset")
    pool.reset()
    assert not pool._flights and not pool._firsts
    assert pool.step() is False


# -- one download a tick, and nothing else comes off the device --------------

class _NumpySpy(types.ModuleType):
    """Stands for ``numpy`` in a pool's module: what it is asked to make
    of a device array is a read the tick does not own."""

    def __init__(self, reads):
        super().__init__("numpy")
        self.__dict__["_reads"] = reads

    def __getattr__(self, name):
        real = getattr(np, name)
        if name not in ("asarray", "array", "ascontiguousarray"):
            return real

        def made(a, *args, **kw):
            if isinstance(a, jax.Array):
                self._reads.append("np.%s" % name)
            return real(a, *args, **kw)
        return made


@contextlib.contextmanager
def device_reads(monkeypatch):
    """``(gets, strays, waits)``: calls of ``jax.device_get``; every
    other way a device array's value reaches the host (``int()``,
    ``bool()``, ``.tolist()``, ``.item()``, printing, ``np.asarray`` in
    the pools' modules); and calls of ``block_until_ready``, which reads
    nothing but does wait."""
    from jax._src import array as jarray

    gets, strays, waits, inside = [], [], [], []
    real_get, real_value = jax.device_get, jarray.ArrayImpl._value

    def device_get(x):
        gets.append(1)
        inside.append(1)
        try:
            return real_get(x)
        finally:
            inside.pop()

    def value(self):
        if self._npy_value is None and not inside:
            strays.append("value of %s%s" % (self.dtype, self.shape))
        return real_value.fget(self)

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", device_get)
        m.setattr(jarray.ArrayImpl, "_value", property(value))
        m.setattr(jax, "block_until_ready",
                  lambda x: waits.append(1) or x)
        for mod in (gen_mod, bd_mod, spec_mod):
            m.setattr(mod, "np", _NumpySpy(strays))
        yield gets, strays, waits


@pytest.mark.parametrize("kind", sorted(KINDS) + ["speculative"])
def test_a_tick_makes_one_download_and_no_other_read(request, lm,
                                                     monkeypatch, kind):
    if kind == "speculative":
        pool = SpeculativePool(lm, lm, spec_k=2, max_len=24, slots=2,
                               buckets=[16])
    else:
        pool = build(request, kind)
    for i, (p, n) in enumerate(zip(prompts(), BUDGETS)):
        pool.submit(p, n, request_id=i)
    with trace.tracing(Tracer(capacity=8192)) as tracer:
        with device_reads(monkeypatch) as (gets, strays, waits):
            per_tick, waited, more = [], [], True
            while more:
                before = len(gets), len(waits)
                more = pool.step()
                per_tick.append(len(gets) - before[0])
                waited.append(len(waits) - before[1])
    assert strays == []
    # one download in every tick, the ticks that prefill included; none
    # where a tick found nothing to bring home
    assert set(per_tick) <= {0, 1} and per_tick.count(1) >= 6
    events = tracer.recorder.snapshot()
    prefills = sum(e.name == "tick.prefill" for e in events)
    assert prefills >= 6
    assert sum(e.name == "tick.sample" for e in events) == sum(per_tick)
    # the one wait there is: a SECOND prefill of one admit phase waits
    # for the first (two row caches alive at most); a tick that admits
    # one request, as a steady tick does, waits for nothing
    if "chunked" in kind:
        assert sum(waited) == 0         # no row cache: nothing to bound
    else:
        assert waited[0] == 1 and set(waited) <= {0, 1}
        assert sum(waited) < prefills - 1


# -- the same executables ----------------------------------------------------

PARENTS_COUNTS = {
    # what the pools compiled for this work before the host ran a step
    # behind (one bucket): ``block_step`` has a new signature and is
    # still ONE executable
    "plain-dense": {"prefill": 1, "decode": 0, "pool_decode": 1,
                    "slot_insert": 1},
    "plain-paged": {"prefill": 1, "decode": 0, "pool_decode": 1,
                    "slot_insert": 1},
    "plain-chunked": {"prefill": 0, "decode": 0, "pool_decode": 1,
                      "slot_insert": 0, "prefill_chunk": 1,
                      "slot_admit": 1},
    "block-dense": {"block_prefill": 1, "block_step": 1, "slot_insert": 1},
    "block-paged": {"block_prefill": 1, "block_step": 1, "slot_insert": 1},
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_compile_counts_are_the_parents(request, kind):
    pool = build(request, kind)
    drain(pool)
    level = build(request, kind, sync=True)
    drain(level)
    assert pool.compile_counts() == level.compile_counts() \
        == PARENTS_COUNTS[kind]
