"""BENCHMARK.json keeps to the contract's letters, and every file it names
is there."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert m["paths"] == ["benchmark"]
    assert m["command"][1].startswith("benchmark/")


def test_names_units_and_one_line_texts(m):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({e["name"] for e in metrics}) == len(metrics)
    for e in metrics:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
    for e in m["configs"] + m["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] \
            and "\t" not in e["why"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)


def test_every_named_file_exists(m):
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        path = os.path.join(ROOT, c["file"])
        assert os.path.isfile(path), path
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert all(k in cfg for k in c["reduced"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "harness", cfg["driver"] + ".py"))
        for k in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)",
                                 k), k
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for w in m["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "traffic",
                                           w["traffic"] + ".json"))
    for e in m["end_to_end"]:
        if e["name"] != "setup_s":
            with open(os.path.join(ROOT, "benchmark", "end_to_end",
                                   e["name"] + ".json")) as f:
                s = json.load(f)
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "readers", s["reader"] + ".py"))
    for p in m["per_layer"]:
        spec = os.path.join(ROOT, "benchmark", "metrics",
                            p["name"] + ".json")
        assert os.path.isfile(spec), spec
        with open(spec) as f:
            s = json.load(f)
        assert s["layer"] == p["layer"] and s["unit"] == p["unit"] \
            and s["moves"] == p["moves"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                           s["reader"] + ".py"))
    for root, _dirs, names in os.walk(os.path.join(ROOT, "benchmark")):
        if "_trace" in root or "__pycache__" in root:
            continue
        for n in names:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", n), os.path.join(root, n)


def test_cells_and_metrics_line_up(m):
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"] for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == configs
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    layers = {}
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        moved = e2e[p["moves"]].get("workloads", list(cells))
        for w in p.get("workloads", moved):
            assert w in cells and w in moved, (p["name"], w)
        layers.setdefault(p["layer"], []).append(p["name"])
        assert 1 <= len(p["layer"]) <= 200 and "\n" not in p["layer"]
    for name in cells:
        reported = [e for e in m["end_to_end"]
                    if name in e.get("workloads", [name])]
        assert len(reported) >= 2, name
        assert any(name in p.get("workloads", []) for p in m["per_layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, "PERF.md's list of layers lacks %r" % layer
