"""The bench regression reporter (tools/bench_report.py).

Pure-stdlib and fast — this module is part of the tier-1 CI wiring:
``test_check_passes_with_no_history_present`` runs the real
``python -m tools.bench_report --check`` contract at the reporter's
default paths (in-process, no subprocess, no jax import) — the repo
commits no history file, and a fresh history passes by definition — and
the synthetic cases pin that the gate actually FAILS on a regressed
record — a reporter that always passes is not a gate."""
import copy
import io
import json
import os
from contextlib import redirect_stdout

from tools.bench_report import (DEFAULT_HISTORY, DEFAULT_ROUNDS,
                                build_report, diff_leg, flatten_metrics,
                                load_history, load_round_files, main,
                                render_markdown)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record(rev, legs, backend="tpu (test)", at="2026-01-01T00:00:00Z"):
    return {"measured_at": at, "git_rev": rev, "backend": backend,
            "legs": legs}


BASE_LEGS = {
    "decode": {
        "tokens_per_sec": 1000.0,
        "dense_fp32_batch1": {"per_token_s": 0.001,
                              "decode_tokens_per_sec": 1000.0,
                              "kv_reachable_bytes": 4096},
    },
    "serving": {"tokens_per_sec": 800.0,
                "batch8": {"ttft_p95_s": 0.2, "tokens_per_sec": 800.0}},
    "bert": {"tokens_per_sec": 120000.0, "mfu": 0.43},
}


def _history_file(tmp_path, records):
    path = tmp_path / "history.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    return str(path)


def _run(argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = main(argv)
    return rc, out.getvalue()


# -- the CI gate at the default paths -------------------------------------

def test_check_passes_with_no_history_present():
    # the repo commits no history file: the reporter must keep working
    # and say out loud that nothing was gated
    assert not os.path.exists(DEFAULT_HISTORY)
    assert load_history(DEFAULT_HISTORY) == []
    assert load_round_files(DEFAULT_ROUNDS) == []
    rc, out = _run(["--history", DEFAULT_HISTORY,
                    "--rounds", DEFAULT_ROUNDS, "--check"])
    assert rc == 0, out
    assert "nothing to diff" in out


# -- synthetic regression / improvement cases -----------------------------

def test_check_fails_on_synthetic_regression(tmp_path):
    regressed = copy.deepcopy(BASE_LEGS)
    regressed["decode"]["tokens_per_sec"] = 500.0           # -50% tok/s
    regressed["serving"]["batch8"]["ttft_p95_s"] = 0.5      # +150% TTFT
    path = _history_file(tmp_path, [_record("aaa", BASE_LEGS),
                                    _record("bbb", regressed)])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 1
    assert "FAIL" in out
    assert "tokens_per_sec" in out and "ttft_p95_s" in out
    # without --check the report renders but never gates
    rc, _ = _run(["--history", path, "--rounds", ""])
    assert rc == 0


def test_json_report_shape(tmp_path):
    regressed = copy.deepcopy(BASE_LEGS)
    regressed["bert"]["mfu"] = 0.2
    path = _history_file(tmp_path, [_record("aaa", BASE_LEGS),
                                    _record("bbb", regressed)])
    rc, out = _run(["--history", path, "--rounds", "", "--json",
                    "--check"])
    assert rc == 1
    report = json.loads(out)
    assert report["exit_code"] == 1
    (reg,) = report["regressions"]
    assert reg == {"leg": "bert", "metric": "mfu", "prev": 0.43,
                   "latest": 0.2, "status": "regressed",
                   "direction": "higher", "threshold": 0.10,
                   "delta_pct": -53.49}


def test_within_threshold_and_improvements_pass(tmp_path):
    wobbly = copy.deepcopy(BASE_LEGS)
    wobbly["decode"]["tokens_per_sec"] = 950.0   # -5%: inside ±10%
    wobbly["bert"]["tokens_per_sec"] = 200000.0  # +67%: improvement
    path = _history_file(tmp_path, [_record("aaa", BASE_LEGS),
                                    _record("bbb", wobbly)])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 0, out
    report = build_report([_record("aaa", BASE_LEGS),
                           _record("bbb", wobbly)])
    assert not report["regressions"]
    assert any(r["metric"] == "tokens_per_sec" and r["leg"] == "bert"
               for r in report["improvements"])


def test_cross_backend_records_never_compared(tmp_path):
    # a CPU smoke run after a TPU record must not "regress" everything
    # 100x: the reporter only pairs same-backend records
    cpu = copy.deepcopy(BASE_LEGS)
    cpu["decode"]["tokens_per_sec"] = 5.0
    path = _history_file(tmp_path, [
        _record("aaa", BASE_LEGS, backend="tpu (v5e)"),
        _record("bbb", cpu, backend="cpu",
                at="2026-01-02T00:00:00Z")])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 0
    assert "backend" in out  # the skip is said out loud, not silent


def test_missing_and_new_legs_are_notes_not_failures(tmp_path):
    latest = {"decode": dict(BASE_LEGS["decode"]),
              "brand_new_leg": {"tokens_per_sec": 1.0}}
    report = build_report([_record("aaa", BASE_LEGS),
                           _record("bbb", latest,
                                   at="2026-01-02T00:00:00Z")])
    assert not report["regressions"]
    notes = " ".join(report["notes"])
    assert "brand_new_leg" in notes and "serving" in notes


def test_flatten_and_untracked_metrics():
    flat = flatten_metrics(BASE_LEGS["decode"])
    assert flat["tokens_per_sec"] == 1000.0
    assert flat["dense_fp32_batch1.per_token_s"] == 0.001
    rows = diff_leg("decode", BASE_LEGS["decode"],
                    BASE_LEGS["decode"])
    assert all(r["status"] in ("ok", "untracked") for r in rows)
    # an unknown metric never gates, even when it moves wildly
    rows = diff_leg("x", {"mystery_stat": 1.0}, {"mystery_stat": 99.0})
    assert rows[0]["status"] == "untracked"


def test_markdown_renders_flagged_table(tmp_path):
    regressed = copy.deepcopy(BASE_LEGS)
    regressed["decode"]["dense_fp32_batch1"]["per_token_s"] = 0.01
    report = build_report([_record("aaa", BASE_LEGS),
                           _record("bbb", regressed,
                                   at="2026-01-02T00:00:00Z")])
    md = render_markdown(report)
    assert "# Bench regression report" in md
    assert "| dense_fp32_batch1.per_token_s |" in md
    assert "**regressed**" in md


def test_duplicate_records_never_pair_with_themselves(tmp_path):
    # a round wrapper and the history line it was promoted into are
    # the SAME run: pairing them would diff a run against itself and
    # hide every real regression behind a 0% self-comparison
    regressed = copy.deepcopy(BASE_LEGS)
    regressed["decode"]["tokens_per_sec"] = 500.0
    path = _history_file(tmp_path, [
        _record("aaa", BASE_LEGS, at="2026-01-01T00:00:00Z"),
        _record("bbb", regressed, at="2026-01-02T00:00:00Z"),
        _record("bbb", regressed, at="2026-01-02T00:00:00Z"),  # dup
    ])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 1  # the dup collapses; aaa-vs-bbb still compares
    assert "tokens_per_sec" in out


def test_single_record_history_passes(tmp_path):
    path = _history_file(tmp_path, [_record("aaa", BASE_LEGS)])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 0
    assert "fewer than 2" in out


def _lora_legs(adapters=8):
    legs = copy.deepcopy(BASE_LEGS)
    legs["serving_lora"] = {
        "tokens_per_sec": 1100.0,
        "adapters_1": {"tokens_per_sec": 1150.0, "adapters": 1},
        "shared_8": {"tokens_per_sec": 1100.0, "adapters": adapters},
        "dedicated_8": {"tokens_per_sec": 600.0, "adapters": 8},
    }
    return legs


def test_structural_gate_refuses_unadapted_lora_leg(tmp_path):
    # a timed serving_lora sub-leg must carry its numeric adapters
    # stamp: --check fails on the LATEST record even with no diff pair
    bad = _lora_legs(adapters=None)
    path = _history_file(tmp_path, [_record("aaa", bad)])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 1
    assert "STRUCTURAL" in out and "'shared_8'" in out \
        and "'adapters'" in out
    assert "1 structural" in out
    # a BOOL stamp is refused the same way (True is not a count)
    path = _history_file(tmp_path, [_record("bbb", _lora_legs(True))])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 1 and "STRUCTURAL" in out
    # without --check the violation is reported but never gates
    rc, _ = _run(["--history", path, "--rounds", ""])
    assert rc == 0


def test_structural_gate_passes_stamped_lora_leg(tmp_path):
    path = _history_file(tmp_path, [_record("aaa", _lora_legs()),
                                    _record("bbb", _lora_legs(),
                                            at="2026-01-02T00:00:00Z")])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 0 and "STRUCTURAL" not in out
    # only the LATEST record is gated: an old unstamped record must
    # not brick the history forever
    path = _history_file(tmp_path, [
        _record("aaa", _lora_legs(adapters=None)),
        _record("bbb", _lora_legs(), at="2026-01-02T00:00:00Z")])
    rc, out = _run(["--history", path, "--rounds", "", "--check"])
    assert rc == 0


def test_structural_violation_rides_json_report(tmp_path):
    path = _history_file(tmp_path,
                         [_record("aaa", _lora_legs(adapters=None))])
    rc, out = _run(["--history", path, "--rounds", "", "--json",
                    "--check"])
    assert rc == 1
    report = json.loads(out)
    assert report["exit_code"] == 1
    rows = report["structural_violations"]
    assert [r["metric"] for r in rows] == ["shared_8.adapters"]
    assert rows[0]["leg"] == "serving_lora"
    assert rows[0]["status"] == "invalid"
    assert "numeric 'adapters' stamp" in rows[0]["reason"]
