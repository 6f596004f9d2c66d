"""The readers of what a thread waited and of what the front did with a
token: ``span_offcpu`` (a span's length less the ``cpu_s`` in its meta, and
with ``less`` the same of the named spans inside it taken off) and
``instant_meta`` (sums and quantiles over the meta of instants such as
``http.stream``).  Each on hand-built spans whose answer is known, on spans
that lack the keys (None, as on a program that does not write them), through
the data files of the metrics that use them, and on the traced toy cell."""
import importlib
import json
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_manifest.json")
NEW = ("tick_decode_ms", "tick_prep_ms", "tick_unspanned_ms",
       "tick_decode_wait_ms", "tick_host_wait_ms",
       "tick_preempted_per_tick", "http_write_lag_mean_ms",
       "http_write_lag_max_p90_ms", "http_write_cpu_us")


def rd(name):
    return importlib.import_module("readers." + name).read


def span(name, start_ms, end_ms, **meta):
    if "cpu_ms" in meta:
        meta["cpu_s"] = meta.pop("cpu_ms") * 1e-3
    return (name, start_ms * 1e-3, end_ms * 1e-3, None, meta)


def instant(name, at_ms, rid, **meta):
    return (name, at_ms * 1e-3, at_ms * 1e-3, rid, meta)


def ctx_of(spans, t1=0.050):
    return {"spans": spans, "t0": 0.0, "t1": t1}


def two_ticks():
    """Two ticks inside the stretch and one cut by its end.  The first, 10
    ms with 4 on the CPU, waits 6: 1.5 of them in its sample (2 ms, 0.5 on
    the CPU) and 2.5 in a prefill (3 ms, 0.5), so 2 for the host.  Its
    dispatch is 1 ms with 0.25 on the CPU.  The second, 12 ms with 3 on the
    CPU, waits 9: 7.5 in its sample, 1.5 for the host; a sample of the tick
    BEFORE it (15-19.5) is no child of it, and a prefill that begins inside
    it and ends after it is not inside it."""
    return [
        span("tick", 0, 10, cpu_ms=4, nivcsw=1, nvcsw=7),
        span("tick.prefill", 2, 5, cpu_ms=0.5),
        span("tick.decode", 6, 7, cpu_ms=0.25),
        span("tick.sample", 7, 9, cpu_ms=0.5),
        span("tick.sample", 15, 19.5, cpu_ms=0.5),
        span("tick", 20, 32, cpu_ms=3, nivcsw=0, nvcsw=9),
        span("tick.decode", 21, 22, cpu_ms=1),
        span("tick.sample", 22, 30, cpu_ms=0.5),
        span("tick.prefill", 31, 33, cpu_ms=0.1),
        span("tick", 45, 53, cpu_ms=1),
        span("tick.decode", 46, 47, cpu_ms=0.5),
    ]


def test_span_offcpu_is_the_length_less_the_threads_cpu_time():
    read = rd("span_offcpu")
    ctx = ctx_of(two_ticks())
    assert read(ctx, {"span": "tick"}) == pytest.approx((6 + 9) / 2)
    # the third dispatch lies inside the stretch though its tick does not
    assert read(ctx, {"span": "tick.decode"}) == pytest.approx(
        (0.75 + 0.0 + 0.5) / 3)


def test_less_takes_off_only_the_named_spans_inside_the_span():
    read = rd("span_offcpu")
    ctx = ctx_of(two_ticks())
    params = {"span": "tick", "less": ["tick.sample", "tick.prefill"]}
    assert read(ctx, params) == pytest.approx((2.0 + 1.5) / 2)
    assert read(ctx, dict(params, less=["tick.sample"])) == pytest.approx(
        (4.5 + 1.5) / 2)
    # a name that does not occur takes nothing off
    assert read(ctx, dict(params, less=["tick.nothing"])) == \
        pytest.approx(7.5)


def test_span_offcpu_leaves_out_what_lacks_cpu_s_and_reads_none():
    read = rd("span_offcpu")
    spans = two_ticks()
    # the parent's program: the same spans without the key
    bare = [(n, s, e, rid, {k: v for k, v in m.items() if k != "cpu_s"})
            for n, s, e, rid, m in spans]
    for params in ({"span": "tick"}, {"span": "tick.decode"},
                   {"span": "tick", "less": ["tick.sample"]}):
        assert read(ctx_of(bare), params) is None
    assert read(ctx_of([]), {"span": "tick"}) is None
    assert read({"spans": None, "t0": 0.0, "t1": 1.0},
                {"span": "tick"}) is None
    assert read(ctx_of(spans), {"span": "tick.govern"}) is None
    # one tick without it is left out; a child without it takes nothing off
    mixed = [bare[0]] + spans[1:5] + [spans[5], spans[6], bare[7]]
    assert read(ctx_of(mixed), {"span": "tick"}) == pytest.approx(9.0)
    assert read(ctx_of(mixed), {"span": "tick", "less": ["tick.sample"]}) \
        == pytest.approx(9.0)


def streams():
    """Three requests ended in the stretch, one after it, one that streamed
    no stamped line, and one instant of another name."""
    return [
        instant("http.stream", 5, "a", lines=10, lag_sum_s=0.020,
                lag_max_s=0.008, cpu_s=0.0010),
        instant("http.stream", 9, "b", lines=30, lag_sum_s=0.030,
                lag_max_s=0.002, cpu_s=0.0020),
        instant("http.stream", 12, "c", lines=0, lag_sum_s=0.0,
                lag_max_s=0.0, cpu_s=0.0),
        instant("req.done", 12, "c", tokens=3),
        instant("http.stream", 40, "d", lines=60, lag_sum_s=0.010,
                lag_max_s=0.004, cpu_s=0.0010),
        instant("http.stream", 70, "e", lines=1000, lag_sum_s=9.0,
                lag_max_s=9.0, cpu_s=9.0),
    ]


def test_instant_meta_ratio_and_quantile():
    read = rd("instant_meta")
    ctx = ctx_of(streams())
    lag = {"instant": "http.stream", "num": "lag_sum_s", "den": "lines",
           "scale": 1e3}
    # a mean a LINE: the sums divided, not the mean of the requests' means
    assert read(ctx, lag) == pytest.approx(1e3 * 0.060 / 100)
    assert read(ctx, dict(lag, num="cpu_s", scale=1e6)) == \
        pytest.approx(1e6 * 0.004 / 100)
    assert read(ctx, {"instant": "http.stream", "num": "cpu_s",
                      "den": "lines"}) == pytest.approx(0.004 / 100)
    worst = {"instant": "http.stream", "key": "lag_max_s", "q": 0.9,
             "scale": 1e3}
    # over 0, 2, 4 and 8 ms, by linear interpolation
    assert read(ctx, worst) == pytest.approx(4 + 0.7 * 4)
    assert read(ctx, dict(worst, q=0.5)) == pytest.approx(3.0)
    # the stretch decides by when the instant was emitted
    assert read(ctx_of(streams(), t1=0.010), lag) == \
        pytest.approx(1e3 * 0.050 / 40)


def test_instant_meta_reads_none_where_nothing_is_emitted():
    read = rd("instant_meta")
    lag = {"instant": "http.stream", "num": "lag_sum_s", "den": "lines"}
    worst = {"instant": "http.stream", "key": "lag_max_s", "q": 0.9}
    for spans in ([], None, [instant("req.done", 3, "a", tokens=3)],
                  # only a request that streamed no stamped line
                  [streams()[2]]):
        assert read({"spans": spans, "t0": 0.0, "t1": 1.0}, lag) is None
    for spans in ([], None, [instant("http.stream", 3, "a", lines=2)]):
        assert read({"spans": spans, "t0": 0.0, "t1": 1.0}, worst) is None


def new_entries():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return manifest, [p for p in manifest["per_layer"]
                      if p["name"].rsplit(".", 1)[0] in NEW]


def test_the_new_metrics_are_appended_each_in_its_cell():
    manifest, new = new_entries()
    assert len(new) == 20
    assert manifest["per_layer"][-20:] == new
    suffix = {"jamba3b-batch-closed": "jamba", "gpt1p3b-batch-closed":
              "batch", "gpt1p3b-chat-r60": "chat"}
    reports = {w["name"]: [m["name"] for m in manifest["end_to_end"]
                           if w["name"] in m.get("workloads", [w["name"]])]
               for w in manifest["workloads"]}
    for p in new:
        (cell,) = p["workloads"]
        assert p["name"].endswith("." + suffix[cell])
        assert p["source"] == "program_span" and p["better"] == "lower"
        assert p["moves"] in reports[cell]
        spec = bench_run.load_json(bench_run.HERE, "metrics",
                                   p["name"] + ".json")
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (p["layer"], p["unit"], p["moves"])
        importlib.import_module("readers." + spec["reader"])


def test_each_new_metric_reads_the_spans_through_its_own_file():
    """Every data file's parameters are ones its reader takes: a value on
    spans that carry the keys, None on the same spans without them for the
    sixteen that need the new keys or the new span."""
    _, new = new_entries()
    spans = two_ticks() + [span("tick.prep", 5.5, 6, cpu_ms=0.5, rows=2,
                                uploaded=1)] + streams()
    bare = [(n, s, e, rid, {}) for n, s, e, rid, m in spans
            if n not in ("tick.prep", "http.stream")]
    lacking = 0
    for p in new:
        ctx = ctx_of(spans)
        got = bench_run.read_metrics([p], "metrics", ctx)
        assert got[p["name"]]["value"] >= 0, p["name"]
        lacking += not bench_run.read_metrics([p], "metrics", ctx_of(bare))
    assert lacking == 16


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
        rc = bench_run.main(["--workload", "toy-closed", "--seed", "13",
                             "--seconds", "1", "--trace", "1"],
                            require_chip=False, hooks={"manifest": TOY})
    finally:
        bench_run.read_metrics = sound
    assert rc == 0
    return seen


def test_the_new_metrics_on_the_traced_toy_cell(toy_ctx):
    _, new = new_entries()
    got = bench_run.read_metrics(
        [p for p in new if p["name"].endswith(".jamba")], "metrics", toy_ctx)
    assert len(got) == 9, sorted(got)
    val = {k.rsplit(".", 1)[0]: v["value"] for k, v in got.items()}
    assert all(v >= 0 for v in val.values()), val
    tick = rd("span_self")(toy_ctx, {"span": "tick", "children": []})
    # time was renamed, not added: the three lie inside a tick
    assert val["tick_decode_ms"] + val["tick_prep_ms"] \
        + val["tick_unspanned_ms"] < tick
    assert val["tick_decode_wait_ms"] <= val["tick_decode_ms"]
    assert val["tick_host_wait_ms"] < tick
    assert val["http_write_lag_mean_ms"] <= val["http_write_lag_max_p90_ms"]
    # every request that ended in the stretch said what its lines waited
    ended = {rid for n, s, _, rid, _ in toy_ctx["spans"]
             if n == "req.done" and toy_ctx["t0"] <= s < toy_ctx["t1"]}
    said = {rid for n, s, _, rid, m in toy_ctx["spans"]
            if n == "http.stream"}
    assert ended and ended <= said
