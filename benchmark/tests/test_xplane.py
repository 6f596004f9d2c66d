"""The trace reducer on a small stored ``.xplane.pb`` whose intervals are
known.  ``data/synthetic.xplane.pb`` was written with the profiler's own
protobuf schema: two TPU planes (the second 1 ms later), each with two
runs of ``jit__pool_decode`` (10-30 ms, 40-60 ms) and one of
``jit__prefill`` (32-37 ms); inside them ``fusion.1`` 5 + 6 ms,
``_paged_call.3`` 13 + 12 ms, ``fusion.2`` 2 + 1 ms, ``fusion.9`` 5 ms, an
``all-reduce.5`` of 2 ms whose second half runs under ``fusion.2`` (its first half under nothing), and a
10 us ``copy.4`` 20 us after the last step; ``bench.sync`` at 5 and 70 ms.
All times start 1 us into the trace (the lines' base)."""
import os

import pytest

from harness import xplane

PB = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                  "data", "synthetic.xplane.pb")
B = 1e-6        # the lines' base timestamp


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(PB)


def test_planes_and_lines(trace):
    assert sorted(trace.devices) == [0, 1]
    assert len(trace.devices[0]["ops"]) == 9
    assert len(trace.devices[0]["modules"]) == 3
    assert "python3" in trace.host


def test_busy_idle_and_per_operation_times(trace):
    busy = xplane.busy_seconds(trace, 0.0, 0.08)
    assert busy == pytest.approx(0.04501)       # 20 + 5 + 20 + 0.01 ms
    assert 1 - busy / 0.08 == pytest.approx(0.437375)
    ops = xplane.op_seconds(trace, 0.0, 0.08)
    assert ops["_paged_call.3"] == pytest.approx(0.025)
    assert ops["fusion.1"] == pytest.approx(0.011)
    assert ops["all-reduce.5"] == pytest.approx(0.002)
    assert xplane.top(ops, 2)[0][0] == "_paged_call.3"
    # a window that cuts an operation counts only the part inside
    assert xplane.op_seconds(trace, 0.0, 0.020 + B)["_paged_call.3"] == \
        pytest.approx(0.005)


def test_executable_runs_and_what_ran_inside_them(trace):
    runs = xplane.module_runs(trace, "jit__pool_decode", 0.0, 0.08)
    assert [round(e - s, 6) for s, e in runs] == [0.02, 0.02]
    assert len(xplane.module_runs(trace, r"jit__prefill(?!_chunk)", 0, 1)) \
        == 1
    # a run that is cut by the window's edge is left out
    assert len(xplane.module_runs(trace, "jit__pool_decode", 0.0, 0.05)) == 1
    inside = xplane.ops_within(trace, runs)
    assert inside["_paged_call.3"] == pytest.approx(0.025)
    assert "fusion.9" not in inside and "copy.4" not in inside
    assert sum(inside.values()) == pytest.approx(0.041)     # overlap twice


def test_idle_gaps_go_to_the_span_that_holds_them(trace):
    spans = [("tick", 0.0, 0.035), ("tick.admit", 0.030, 0.033),
             ("tick", 0.035, 0.0601)]
    gaps = xplane.idle_gaps(trace, spans, 0.0, 0.08)
    assert gaps["tick.admit"] == pytest.approx(0.002)       # 30-32 ms
    assert gaps["tick"] == pytest.approx(0.010001 + 0.003)  # 0-10, 37-40
    assert gaps["pauses_under_50us"] == pytest.approx(20e-6)
    assert gaps["outside_spans"] == pytest.approx(0.08 - 0.060031)
    assert sum(gaps.values()) == pytest.approx(
        0.08 - xplane.busy_seconds(trace, 0.0, 0.08) - 0.0)  # device 0


def test_clock_offset_from_the_sync_marks(trace):
    assert trace.sync_offset([100.0, 100.065]) == pytest.approx(
        0.005 + B - 100.0)
    with pytest.raises(ValueError):
        trace.sync_offset([1.0])


def test_readers_on_the_stored_trace(trace):
    import importlib
    ctx = {"trace": trace, "t0": 0.0, "t1": 0.08, "offset": 0.0,
           "busy_s": xplane.busy_seconds(trace, 0.0, 0.08),
           "window_s": 0.08, "spans": [("tick", 0.0, 0.035, None, {}),
                                       ("tick", 0.035, 0.0601, None, {})]}
    rd = lambda n: importlib.import_module("readers." + n).read
    assert rd("module_time")(ctx, {"pattern": "jit__pool_decode"}) == \
        pytest.approx(20.0)
    assert rd("module_time")(ctx, {"pattern": "nothing"}) is None
    assert rd("op_share")(ctx, {"pattern": "jit__pool_decode",
                                "op_pattern": "_paged_call"}) == \
        pytest.approx(62.5)
    assert rd("op_share")(ctx, {"pattern": "jit__pool_decode",
                                "op_pattern": "no_such"}) == 0.0
    assert rd("idle_share")(ctx, {}) == pytest.approx(43.7375)
    # tick 1: 35 ms less 25 busy; tick 2: 25.1 ms less 20.01 busy
    assert rd("tick_host")(ctx, {"span": "tick"}) == pytest.approx(
        (10.0 + 5.09) / 2, abs=2e-3)


RECORDED = os.path.join(os.path.dirname(PB), "recorded_toy_closed.xplane.pb")


def test_reducer_on_a_trace_recorded_on_the_chip():
    """``data/recorded_toy_closed.xplane.pb`` is a traced second of the toy
    closed batch on one TPU v5 lite (chip run, PR 25), cut down to the planes
    and lines the reducer reads (device 0's ``XLA Ops`` and ``XLA Modules``,
    the host's ``bench.sync`` marks).  The numbers beside it were computed
    when it was recorded by another algorithm than the reducer's: a sweep
    over sorted interval edges with a count of open operations."""
    import json
    with open(RECORDED.replace(".xplane.pb", ".expected.json")) as f:
        exp = json.load(f)
    t = xplane.Trace.from_file(RECORDED)
    assert len(t.devices[0]["ops"]) == exp["n_ops"]
    assert len(t.devices[0]["modules"]) == exp["n_modules"]
    t0, t1 = exp["first_ns"] * 1e-9 - 1e-6, exp["last_ns"] * 1e-9 + 1e-6
    busy = xplane.busy_seconds(t, t0, t1)
    assert busy == pytest.approx(exp["busy_ns"] * 1e-9, rel=1e-9)
    gaps = xplane.idle_gaps(t, [], t0, t1)
    assert sum(gaps.values()) == pytest.approx(t1 - t0 - busy, rel=1e-9)
    ops = xplane.op_seconds(t, t0, t1)
    for name, ns in exp["top"]:
        assert ops[name] == pytest.approx(ns * 1e-9, rel=1e-9)
    assert xplane.top(ops, 1)[0][0] == exp["top"][0][0]
    runs = xplane.module_runs(t, "jit__pool_decode", t0, t1)
    assert len(runs) == exp["decode_runs"]
    assert sum(e - s for s, e in runs) == pytest.approx(
        exp["decode_ns"] * 1e-9, rel=1e-9)
    assert len([1 for evs in t.host.values() for n, _, _ in evs
                if n == xplane.SYNC_NAME]) == 2
