"""The readers of what the program says of itself: ``final_percentile``
(fields of the terminal line), ``span_self`` and ``span_meta`` (the
engine's spans and their meta), ``scope_share`` (device time by
``jax.named_scope``).  Each on a hand-built context whose answer is known,
on a context that lacks what it reads (None, as on a program that does not
emit it), and on the traced toy cell."""
import importlib
import json
import os
import struct

import pytest

import run as bench_run
from harness import opmeta, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_manifest.json")
PHASES = ["tick.admit", "tick.prefill", "tick.decode", "tick.sample",
          "tick.deliver"]


def rd(name):
    return importlib.import_module("readers." + name).read


def span(name, start_ms, end_ms, **meta):
    return (name, start_ms * 1e-3, end_ms * 1e-3, None, meta)


def three_ticks():
    """Three ticks of 10, 12 and 8 ms.  The first admits one request
    (prefill 2-5 ms inside admit 1-6), decodes 6-7, samples 7-9, delivers
    9-9.5: 1.5 ms in no phase.  The second has no admission: decode
    21-22, sample 22-30, deliver 30-31, 2 ms of its own.  The third began
    before the traced stretch ended and closed after it."""
    return [
        span("tick", 0, 10, tick=1, queued=1, admitted=1, finished=0),
        span("tick.govern", 0, 0.5), span("tick.admit", 1, 6),
        span("tick.prefill", 2, 5, prompt_tokens=100, bucket=128),
        span("tick.decode", 6, 7, live=3, slots=4),
        span("tick.sample", 7, 9), span("tick.deliver", 9, 9.5),
        span("tick", 20, 32, tick=2, queued=0, admitted=0, finished=1),
        span("tick.decode", 21, 22, live=4, slots=4),
        span("tick.sample", 22, 30), span("tick.deliver", 30, 31),
        span("tick.prefill", 40, 41, prompt_tokens=20, bucket=32),
        span("tick", 45, 53, tick=3),
        span("tick.decode", 46, 47, live=2, slots=4),
    ]


def ctx_of(spans, t1=0.050):
    return {"spans": spans, "t0": 0.0, "t1": t1}


def test_span_self_is_the_span_less_the_union_of_its_children():
    read = rd("span_self")
    params = {"span": "tick", "children": PHASES}
    # tick 3 is cut by the stretch's end and left out
    assert read(ctx_of(three_ticks()), params) == pytest.approx(
        (1.5 + 2.0) / 2)
    # with no children named, the span's own length
    assert read(ctx_of(three_ticks()), {"span": "tick", "children": []}) \
        == pytest.approx(11.0)
    assert read(ctx_of(three_ticks()), {"span": "tick.admit",
                                        "children": ["tick.prefill"]}) \
        == pytest.approx(2.0)


def test_span_meta_mean_and_ratio():
    read = rd("span_meta")
    ctx = ctx_of(three_ticks())
    assert read(ctx, {"span": "tick.decode", "key": "live"}) == \
        pytest.approx((3 + 4 + 2) / 3)      # by where a span begins
    assert read(ctx, {"span": "tick.prefill", "num": "prompt_tokens",
                      "den": "bucket", "complement": True}) == \
        pytest.approx(100.0 * (1 - 120 / 160))
    assert read(ctx, {"span": "tick.prefill", "num": "prompt_tokens",
                      "den": "bucket"}) == pytest.approx(75.0)
    assert read(ctx, {"span": "tick", "key": "admitted"}) == \
        pytest.approx(0.5)                  # tick 3 does not say


@pytest.mark.parametrize("reader, params", [
    ("span_self", {"span": "tick", "children": PHASES}),
    ("span_meta", {"span": "tick.decode", "key": "live"}),
    ("span_meta", {"span": "tick.prefill", "num": "prompt_tokens",
                   "den": "bucket", "complement": True}),
])
def test_span_readers_return_none_where_the_program_says_nothing(reader,
                                                                 params):
    # the parent's spans: the names without the meta, or no span at all
    bare = [(n, s, e, rid, {}) for n, s, e, rid, _ in three_ticks()
            if n != "tick"]
    assert rd(reader)(ctx_of(bare), params) is None
    assert rd(reader)(ctx_of([]), params) is None
    assert rd(reader)(ctx_of(None), params) is None


def record(due, done, final, stamps=(1.0,), status="ok"):
    return {"due": due, "done": done, "final": final,
            "stamps": list(stamps), "status": status}


def client_ctx(records):
    return {"records": records, "client_window": (10.0, 20.0),
            "traffic": {"drain_s": 5.0}}


def test_final_percentile_reads_the_terminal_lines_of_the_window():
    read = rd("final_percentile")
    recs = [record(10.0 + k, 30.0, {"lock_wait_s": 0.1 * k,
                                    "queue_wait_s": 0.5})
            for k in range(10)]
    recs.append(record(5.0, 12.0, {"lock_wait_s": 9.0}))    # due before
    recs.append(record(25.0, 40.0, {"lock_wait_s": 9.0}))   # and after
    ctx = client_ctx(recs)
    assert read(ctx, {"field": "lock_wait_s", "q": 0.9}) == \
        pytest.approx(810.0)
    assert read(ctx, {"field": "lock_wait_s", "q": 0.5}) == \
        pytest.approx(450.0)
    assert read(ctx, {"field": "queue_wait_s", "q": 0.9}) == \
        pytest.approx(500.0)
    # over the requests that ENDED in the window: only the early one
    assert read(ctx, {"field": "lock_wait_s", "q": 0.9,
                      "over": "ended"}) == pytest.approx(9000.0)


def test_final_percentile_worst_left_out_and_none():
    read = rd("final_percentile")
    ok = [record(11.0, 15.0, {"lock_wait_s": 0.2, "queue_wait_s": 0.1})]
    # still streaming at the end of the run: left out; never got a token,
    # failed, or ended with no slot taken: the worst, 1e3 * (10 + 5)
    streaming = record(12.0, None, None, status="inflight")
    waiting = record(13.0, None, None, stamps=(), status="inflight")
    failed = record(14.0, 16.0, None, status="failed")
    unslotted = record(15.0, 16.0, {"lock_wait_s": 0.3,
                                    "queue_wait_s": None}, stamps=(),
                       status="failed")
    p = {"field": "lock_wait_s", "q": 1.0}
    assert read(client_ctx(ok + [streaming]), p) == pytest.approx(200.0)
    assert read(client_ctx(ok + [waiting]), p) == pytest.approx(15000.0)
    assert read(client_ctx(ok + [failed]), p) == pytest.approx(15000.0)
    assert read(client_ctx(ok + [unslotted]), p) == pytest.approx(300.0)
    assert read(client_ctx(ok + [unslotted]),
                {"field": "queue_wait_s", "q": 1.0}) == \
        pytest.approx(15000.0)
    # the parent's terminal line has no such field; an empty run
    old = [record(11.0, 15.0, {"ttft_s": 0.2, "total_s": 0.4})]
    assert read(client_ctx(old), p) is None
    assert read(client_ctx([]), p) is None
    assert read(client_ctx(ok), {"field": "lock_wait_s", "q": 0.9,
                                 "over": "ended"}) == pytest.approx(200.0)


# -- scope_share on a synthetic plane ---------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*pairs):
    return b"".join(_field(n, v) for n, v in pairs)


STAT_IDS = {"program_id": 1, "tf_op": 2}
PROGRAM = 9394918986786378365           # above 2**63, as the chip's are


def _event_metadata(key, name, program=None, tf_op=None):
    stats = []
    if program is not None:
        stats.append((5, _msg((1, STAT_IDS["program_id"]), (3, program))))
    if tf_op is not None:
        stats.append((5, _msg((1, STAT_IDS["tf_op"]), (5, tf_op))))
    return (4, _msg((1, key), (2, _msg((1, key), (2, name), *stats))))


def _line(name, events):
    """``events``: (metadata id, start us, length us)."""
    return (3, _msg((2, name), (3, 0), *[
        (4, _msg((1, m), (2, s * 10 ** 6), (3, d * 10 ** 6)))
        for m, s, d in events]))


def synthetic_trace(tmp_path, scoped=True):
    """One device plane: two runs of ``jit__pool_decode`` (100-200 us and
    300-400 us) holding a 30 us matmul under ``lm_head``, a 20 us sort
    under ``sample`` and a 10 us fusion of layer 3's ``linear1``, and one
    run of ``jit__prefill`` (500-600 us) whose ``fusion.1`` has the same
    name as the decode step's and another scope."""
    tf = (lambda s: s) if scoped else (lambda s: None)
    other = PROGRAM + 1
    plane = _msg(
        (1, 1), (2, "/device:TPU:0"),
        _line("XLA Modules", [(1, 100, 100), (1, 300, 100),
                              (2, 500, 100)]),
        _line("XLA Ops", [(3, 110, 30), (4, 150, 20), (5, 180, 10),
                          (3, 310, 30), (4, 350, 20), (5, 380, 10),
                          (6, 510, 80)]),
        _event_metadata(1, "jit__pool_decode(%d)" % PROGRAM),
        _event_metadata(2, "jit__prefill(%d)" % other),
        _event_metadata(
            3, "%fusion.1 = f32[16,50304]{1,0} fusion(f32[16,2048] %p)",
            PROGRAM, tf("jit(_pool_decode)/jit(main)/lm_head/dot_general:")),
        _event_metadata(
            4, "%sort.6 = (f32[16,50304]{1,0}) sort(f32[16,50304] %c)",
            PROGRAM, tf("jit(_pool_decode)/jit(main)/sample/jit(sort)/"
                        "sort:")),
        _event_metadata(
            5, "%fusion.7 = f32[16,8192]{1,0} fusion(f32[16,2048] %h)",
            PROGRAM, tf("jit(_pool_decode)/jit(main)/encoder/layers/3/"
                        "linear1/dot_general:")),
        _event_metadata(
            6, "%fusion.1 = f32[1,128,2048]{2,1,0} fusion(f32[1,128] %x)",
            other, tf("jit(_prefill)/jit(main)/lm_head/dot_general:")),
        *[(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
          for n, i in STAT_IDS.items()])
    folder = tmp_path / "plugins" / "profile" / "run"
    folder.mkdir(parents=True)
    path = folder / "t.xplane.pb"
    path.write_bytes(_msg((1, plane)))
    return str(path)


def test_op_scopes_reads_the_metadata_stats(tmp_path):
    scopes = opmeta.op_scopes(synthetic_trace(tmp_path))
    assert scopes == {
        (PROGRAM, "fusion.1"): "jit(_pool_decode)/jit(main)/lm_head",
        (PROGRAM, "sort.6"):
            "jit(_pool_decode)/jit(main)/sample/jit(sort)",
        (PROGRAM, "fusion.7"):
            "jit(_pool_decode)/jit(main)/encoder/layers/3/linear1",
        (PROGRAM + 1, "fusion.1"): "jit(_prefill)/jit(main)/lm_head",
    }


def test_scope_share_on_a_synthetic_plane_with_its_scopes(tmp_path):
    path = synthetic_trace(tmp_path)
    trace = xplane.Trace.from_file(path)
    assert len(trace.devices[0]["ops"]) == 7        # ProfileData reads it
    ctx = {"trace": trace, "t0": 0.0, "t1": 0.001}
    read = rd("scope_share")
    base = {"pattern": "jit__pool_decode", "trace_dir": str(tmp_path)}
    share = lambda pat: read(ctx, dict(base, scope_pattern=pat))
    assert share(r"/lm_head(/|$)") == pytest.approx(30.0)
    assert share(r"/sample(/|$)") == pytest.approx(20.0)
    assert share(r"/layers/\d+/linear[12](/|$)") == pytest.approx(10.0)
    assert share("^$") is None          # every operation has its scope
    # prefill's fusion.1 is another program's: its own scope, its own runs
    assert read(ctx, {"pattern": "jit__prefill", "trace_dir": str(tmp_path),
                      "scope_pattern": r"/lm_head(/|$)"}) == \
        pytest.approx(80.0)
    # a run cut by the window's edge is left out, as module_time does
    assert read(dict(ctx, t1=0.00035),
                dict(base, scope_pattern=r"/lm_head(/|$)")) == \
        pytest.approx(30.0)


def test_scope_share_is_none_never_zero_without_scopes(tmp_path, capsys):
    read = rd("scope_share")
    params = {"pattern": "jit__pool_decode", "trace_dir": str(tmp_path),
              "scope_pattern": r"/lm_head(/|$)"}
    # an executable the compile cache kept from a program without scopes
    trace = xplane.Trace.from_file(synthetic_trace(tmp_path, scoped=False))
    assert read({"trace": trace, "t0": 0.0, "t1": 0.001}, params) is None
    assert "not reported" in capsys.readouterr().out
    # ... where all of the step is outside any scope, as "^$" reads it
    ctx = {"trace": trace, "t0": 0.0, "t1": 0.001}
    assert read(ctx, dict(params, scope_pattern="^$")) == \
        pytest.approx(60.0)
    # a scope that nothing ran under; an executable that never ran; no chip
    assert read(ctx, dict(params, scope_pattern="/optimizer")) is None
    assert read(ctx, dict(params, pattern="jit__step")) is None
    assert read({"trace": xplane.Trace({}, {}), "t0": 0.0, "t1": 1.0},
                params) is None


# -- on the traced toy cell ---------------------------------------------------

@pytest.fixture(scope="module")
def toy_ctx():
    """The context of one traced run of the toy closed batch, as the
    readers are given it."""
    seen = {}
    sound = bench_run.read_metrics

    def spy(entries, folder, ctx):
        seen.update(ctx)
        return sound(entries, folder, ctx)

    bench_run.read_metrics = spy
    try:
        rc = bench_run.main(["--workload", "toy-closed", "--seed", "11",
                             "--seconds", "1", "--trace", "1"],
                            require_chip=False, hooks={"manifest": TOY})
    finally:
        bench_run.read_metrics = sound
    assert rc == 0
    return seen


def test_readers_on_the_traced_toy_cell(toy_ctx):
    ctx = toy_ctx
    slots = ctx["cfg"]["engine"]["slots"]
    ticks = [e - s for n, s, e, _, _ in ctx["spans"]
             if n == "tick" and s >= ctx["t0"] and e <= ctx["t1"]]
    own = rd("span_self")(ctx, {"span": "tick", "children": PHASES})
    assert 0 < own < 1e3 * sum(ticks) / len(ticks)
    # the three phases of the tick's own work are inside that remainder
    named = sum(rd("span_self")(ctx, {"span": p, "children": []})
                for p in ("tick.govern", "tick.observe", "tick.journal"))
    assert 0 < named <= own
    live = rd("span_meta")(ctx, {"span": "tick.decode", "key": "live"})
    assert 1 <= live <= slots
    padding = rd("span_meta")(ctx, {"span": "tick.prefill",
                                    "num": "prompt_tokens",
                                    "den": "bucket", "complement": True})
    assert 0 <= padding < 100
    for field in ("lock_wait_s", "queue_wait_s"):
        for over in ("due", "ended"):
            value = rd("final_percentile")(
                ctx, {"field": field, "q": 0.9, "over": over})
            assert value is not None and value >= 0, (field, over)
    # every terminal line carries both, and nothing traced the window
    finals = [r["final"] for r in ctx["records"] if r["final"]]
    assert finals and all(f["lock_wait_s"] >= 0 and f["queue_wait_s"] >= 0
                          for f in finals)
    # the CPU's trace has no device plane: nothing to read a scope from
    assert rd("scope_share")(ctx, {
        "pattern": "jit__pool_decode", "scope_pattern": "/lm_head",
        "trace_dir": "_trace/toy-closed"}) is None


def test_every_new_metric_names_its_reader_and_its_cell():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    new = [p for p in manifest["per_layer"]
           if p["name"].split(".")[0] in (
               "lock_wait_p90_ms", "admit_wait_p90_ms",
               "tick_housekeeping_ms", "live_slots_per_step",
               "prefill_padding_share", "lm_head_share", "sample_share",
               "mlp_share", "attn_proj_share", "optimizer_share",
               "lm_head_loss_share", "unscoped_share")]
    assert len(new) == 15
    # appended after the accepted ones, each in one cell
    assert manifest["per_layer"][-15:] == new
    for p in new:
        spec = bench_run.load_json(bench_run.HERE, "metrics",
                                   p["name"] + ".json")
        (cell,) = p["workloads"]
        if spec["reader"] == "scope_share":
            assert spec["params"]["trace_dir"] == "_trace/" + cell
        importlib.import_module("readers." + spec["reader"])
