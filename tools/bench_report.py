"""Bench regression reporter: diff the perf history, gate on it.

A history file (``BENCH_HISTORY.jsonl`` at the repo root by default; the
repo commits none) holds one bench record per line and ``BENCH_r*.json``
wrap a round's harness output.  This module DIFFS them — a 20% decode
regression must not sit in an artifact unread.  With no history present
it reports that there is nothing to diff and ``--check`` passes.

    python -m tools.bench_report            # markdown report
    python -m tools.bench_report --json     # machine-readable
    python -m tools.bench_report --check    # exit 1 on any regression

It parses every available record, picks the LATEST and the most recent
earlier record with the SAME backend (comparing a CPU smoke run against
a TPU record would "regress" everything 100x), flattens each shared
leg's numeric metrics, and flags changes beyond per-metric thresholds
in the metric's bad direction — throughput/MFU/acceptance falling,
latency/step-time/bytes rising.  Unknown metrics are reported but never
gated (a new stamp must not fail CI the round it lands); missing legs
are noted, not flagged (legs come and go with the harness).

Pure stdlib, no jax import: the reporter must be runnable by CI and
tier-1 tests in milliseconds, and must never touch an accelerator.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_HISTORY = os.path.join(_REPO, "BENCH_HISTORY.jsonl")
DEFAULT_ROUNDS = os.path.join(_REPO, "BENCH_r*.json")

# metric name (the LAST dotted component of the flattened key) ->
# (direction, relative threshold).  Direction names the GOOD way;
# a change beyond the threshold in the other direction is a
# regression.  Thresholds are deliberately loose for noisy wall-clock
# metrics and tight for byte accounting (bytes are deterministic: any
# growth is a real change someone should explain).
THRESHOLDS: Dict[str, Tuple[str, float]] = {
    # throughput family: higher is better
    "tokens_per_sec": ("higher", 0.10),
    "decode_tokens_per_sec": ("higher", 0.10),
    "imgs_per_sec": ("higher", 0.10),
    "mfu": ("higher", 0.10),
    # sustained HBM bandwidth (tok/s x compiler bytes/token, §5l): the
    # roofline column the fused decode kernel is gated on — falling
    # means either tok/s fell (caught above too) or the executable
    # started streaming fewer accounted bytes per token at the same
    # speed, and both deserve a look
    "bandwidth_util_bytes_per_sec": ("higher", 0.10),
    "acceptance_rate": ("higher", 0.20),
    "speedup_vs_plain": ("higher", 0.20),
    # prefix sharing: a hit-rate drop means the index stopped firing on
    # the same zipf traffic (deterministic corpus, so tight-ish), and
    # the blocks it saves are byte accounting; TTFT-improvement
    # shrinking is gated loosely like the other wall-clock columns
    "prefix_hit_rate": ("higher", 0.10),
    "prefix_blocks_saved_bytes": ("higher", 0.10),
    "ttft_p95_improvement_pct": ("higher_abs", 10.0),
    # traffic-grade scheduling (serving_overload): the closed-loop
    # headline must not silently decay — high-priority p99 TTFT
    # improvement and the burn the ladder buys back are gated on
    # absolute points (both are already relative quantities); the
    # per-class latency columns ride the usual wall-clock thresholds
    "ttft_p99_high_improvement_pct": ("higher_abs", 15.0),
    "slo_burn_drop": ("higher_abs", 3.0),
    # disaggregated serving (serving_disagg, docs §5n): the fused-vs-
    # disagg ITL headline is gated like the TTFT one (absolute points
    # — both are already relative quantities); the hand-off's wire
    # cost is byte accounting (deterministic per config: transfer
    # files carry exactly the committed blocks), so growth is a real
    # contract change someone should explain
    "itl_p95_improvement_pct": ("higher_abs", 10.0),
    "kv_transfer_bytes": ("lower", 0.01),
    "handoff_wait_p95_s": ("lower", 0.50),
    "ttft_p95_high_s": ("lower", 0.40),
    "ttft_p99_high_s": ("lower", 0.40),
    "ttft_p95_low_s": ("lower", 0.40),
    "ttft_p99_low_s": ("lower", 0.40),
    # latency family: lower is better
    "step_time_s": ("lower", 0.15),
    "per_token_s": ("lower", 0.15),
    "per_token_us": ("lower", 0.15),
    "prefill_s": ("lower", 0.25),
    "ttft_p50_s": ("lower", 0.25),
    "ttft_p95_s": ("lower", 0.25),
    "itl_p50_s": ("lower", 0.25),
    "itl_p95_s": ("lower", 0.25),
    "recovery_wall_s": ("lower", 0.30),
    # crash-durable serving (serving_restart, docs §5m): the recovery-
    # time objective — journal replay + resubmit/adoption through the
    # first post-restore token.  Host-side work like recovery_wall_s,
    # gated at the same looseness (CPU smoke jitters with scheduler
    # noise; the tokens_lost==0 contract is the bench gate's job)
    "restore_rto_s": ("lower", 0.30),
    # byte accounting: deterministic, so tight
    "kv_resident_bytes": ("lower", 0.01),
    "kv_reachable_bytes": ("lower", 0.01),
    # cost-model columns (compiler-reported, deterministic per config)
    "cost_flops_per_token": ("lower", 0.01),
    "cost_bytes_per_token": ("lower", 0.01),
    "cost_hbm_reserved_bytes": ("lower", 0.01),
    # tracing price: bounded absolutely by the bench gate at 3%; here
    # gate on growth beyond 3 percentage POINTS
    "trace_overhead_pct": ("lower_abs", 3.0),
    # sharded serving (serving_sharded): the measured-vs-ideal scaling
    # column must not silently decay (it is already a ratio, so gate
    # relative like the throughput family but looser — CPU smoke runs
    # 8 virtual devices on one physical CPU); the per-shard cost/HBM
    # columns are compiler-reported and deterministic per config
    "scaling_efficiency": ("higher", 0.20),
    # serving fleet (serving_fleet, docs §5o): the engine-death
    # recovery objective — hard-abandon through every migrated
    # victim's first post-migration token on a survivor.  Host+replay
    # work like the other RTOs, gated at the same looseness
    "migration_rto_s": ("lower", 0.30),
    # the router's affinity share on the shared-prefix zipf mix: a
    # ratio, but CPU smoke placement jitters with arrival timing —
    # gate loosely; a silent fall to ~0 (router stopped firing) is
    # what this catches
    "prefix_affinity_hit_rate": ("higher", 0.30),
    "cost_flops_per_shard": ("lower", 0.01),
    "cost_bytes_per_shard": ("lower", 0.01),
    "cost_hbm_reserved_per_shard": ("lower", 0.01),
    "kv_resident_bytes_per_shard": ("lower", 0.01),
    # quantized mp collectives (docs §5r): per-token wire bytes of the
    # decode step's activation collectives, computed from the traced
    # shapes — deterministic per config, so tight: growth means either
    # the quantized path widened (scale granularity / block-size
    # change) or a seam silently fell back to the dense ring
    "collective_bytes_per_token": ("lower", 0.01),
    # O(1)-cache model class (decode_ssm, docs §5p): the capacity
    # columns are byte accounting, deterministic per config — a fall
    # in slots/GB (or growth in per-slot state bytes) is a contract
    # change in the model class's whole value proposition, so tight
    "slots_per_gb": ("higher", 0.01),
    "slots_per_gb_ratio": ("higher", 0.01),
    "state_bytes_per_slot": ("lower", 0.01),
    # multi-LoRA serving (serving_lora, docs §5q): the weight columns
    # are byte accounting, deterministic per config — growth in the
    # shared engine's resident weights (or shrinkage of what the bank
    # saves over dedicated engines) is a contract change in the tier's
    # whole value proposition, so tight.  The compile columns are the
    # exactly-two contract itself: adapter ids and sampling are traced
    # DATA, so ANY compile during traffic (or on a hot-load) is a
    # regression — gated at zero absolute growth
    "weight_hbm_bytes": ("lower", 0.01),
    "adapter_bank_bytes": ("lower", 0.01),
    "weight_bytes_saved": ("higher", 0.01),
    "weight_bytes_ratio": ("lower", 0.01),
    "compiles_during_traffic": ("lower_abs", 0.0),
    "hot_load_compiles": ("lower_abs", 0.0),
}

# per-leg overrides: (leg, metric) -> (direction, threshold).  The
# speculative leg's tokens/s on CPU smoke runs swings with scheduler
# noise far more than the decode marginal does.
PER_LEG_THRESHOLDS: Dict[Tuple[str, str], Tuple[str, float]] = {
    ("speculative", "tokens_per_sec"): ("higher", 0.25),
    ("serving_faults", "tokens_per_sec"): ("higher", 0.25),
    # the overload leg's per-class p50s sit at one-tick granularity on
    # CPU smoke runs — scheduler noise owns them; leave them untracked
    # rather than false-alarming (the p95/p99 columns are gated above)
    ("serving_overload", "ttft_p50_high_s"): ("lower", 1.00),
    ("serving_overload", "ttft_p50_low_s"): ("lower", 1.00),
    # the sharded leg's tok/s on CPU smoke times 8 virtual devices
    # multiplexed onto one physical CPU — scheduler noise owns the
    # absolute number there; the scaling_efficiency ratio (gated
    # above) is the honest cross-run signal
    ("serving_sharded", "tokens_per_sec"): ("higher", 0.30),
    # the fleet leg's tok/s on CPU smoke times N engines multiplexed
    # onto one physical CPU — same caveat as the sharded leg; the
    # scaling/RTO/affinity ratios above are the cross-run signal
    ("serving_fleet", "tokens_per_sec"): ("higher", 0.30),
    # the disagg leg's improvement columns sit near zero on CPU smoke
    # (both tiers timeshare one core — the split buys nothing there),
    # so single-digit-point jitter is all noise; gate loosely and let
    # the on-chip run's thresholds ride the global entries
    ("serving_disagg", "ttft_p95_improvement_pct"): ("higher_abs", 40.0),
    ("serving_disagg", "itl_p95_improvement_pct"): ("higher_abs", 40.0),
    # the lora leg's dedicated sub-leg times 8 engines multiplexed
    # onto one CPU on smoke runs — same caveat as the fleet leg; the
    # weight-byte and compile columns above are the cross-run signal
    ("serving_lora", "tokens_per_sec"): ("higher", 0.30),
}

# structural requirements on the LATEST record, enforced by --check
# even when there is no earlier record to diff against: a timed
# sub-leg (a dict stamped with tokens_per_sec) of these legs must
# carry the named numeric columns.  A serving_lora number that cannot
# say how many fine-tunes it mixed claims nothing — the reporter
# REFUSES it rather than letting an unstamped record seed the history
# the next round diffs against.
STRUCTURAL_REQUIRED: Dict[str, Tuple[str, ...]] = {
    "serving_lora": ("adapters",),
}


def validate_structure(record: dict) -> List[dict]:
    """Violation rows for structurally-invalid legs of one record."""
    rows: List[dict] = []
    for leg_name, required in sorted(STRUCTURAL_REQUIRED.items()):
        leg = (record.get("legs") or {}).get(leg_name)
        if not isinstance(leg, dict):
            continue
        timed = {k: v for k, v in leg.items()
                 if isinstance(v, dict) and "tokens_per_sec" in v}
        for sub, metrics in sorted(timed.items()):
            for field in required:
                val = metrics.get(field)
                if isinstance(val, bool) or \
                        not isinstance(val, (int, float)):
                    rows.append({
                        "leg": leg_name,
                        "metric": "%s.%s" % (sub, field),
                        "prev": None, "latest": None,
                        "status": "invalid",
                        "direction": "higher_abs", "threshold": 0.0,
                        "delta_pct": None,
                        "reason": ("timed sub-leg %r is missing the "
                                   "numeric %r stamp" % (sub, field)),
                    })
    return rows


def load_history(path: str,
                 notes: Optional[List[str]] = None) -> List[dict]:
    """Records from the append-only history file (oldest first).
    Malformed or leg-less lines are skipped, and each skip is appended
    to ``notes`` (when given) so a run missing from the diff is
    explained in the report, not silently absent."""
    records = []
    if not os.path.exists(path):
        return records
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            where = "%s:%d" % (os.path.basename(path), i + 1)
            try:
                rec = json.loads(line)
            except ValueError:
                if notes is not None:
                    notes.append("%s: unparseable line skipped"
                                 % where)
                continue
            if isinstance(rec, dict) and isinstance(rec.get("legs"),
                                                    dict):
                rec["_source"] = where
                records.append(rec)
            elif notes is not None:
                notes.append("%s: record without a legs dict skipped"
                             % where)
    return records


def _record_from_result(parsed: dict, source: str) -> Optional[dict]:
    """A history-shaped record from one bench.py result line
    (``{"metric", ..., "extra": {...}}``), taking live legs when
    present and falling back to the promoted stored legs."""
    extra = parsed.get("extra")
    if not isinstance(extra, dict):
        return None
    legs = extra.get("legs") or extra.get("stored_legs")
    if not isinstance(legs, dict) or not legs:
        return None
    return {
        "measured_at": extra.get("measured_at"),
        "git_rev": extra.get("git_rev"),
        "backend": extra.get("backend"),
        "legs": {k: v for k, v in legs.items() if isinstance(v, dict)},
        "_source": source,
    }


def load_round_files(pattern: str) -> List[dict]:
    """Best-effort records from the ``BENCH_r*.json`` round wrappers:
    use the pre-parsed result when the wrapper carries one, else try
    the last JSON line of the captured tail (often truncated — a
    truncated tail is simply skipped, never guessed at)."""
    records = []
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path) as f:
                wrapper = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(wrapper, dict):
            continue
        parsed = wrapper.get("parsed")
        if not isinstance(parsed, dict):
            tail = wrapper.get("tail") or ""
            for line in reversed(tail.strip().splitlines()):
                if line.startswith("{"):
                    try:
                        parsed = json.loads(line)
                    except ValueError:
                        parsed = None
                    break
        if isinstance(parsed, dict):
            rec = _record_from_result(parsed, os.path.basename(path))
            if rec is not None:
                records.append(rec)
    return records


def flatten_metrics(leg: dict, prefix: str = "") -> Dict[str, float]:
    """Dotted-key map of every numeric metric in a leg, sub-legs
    included (lists — sweep tables — are skipped: they are records,
    not comparable scalars)."""
    out: Dict[str, float] = {}
    for key, value in leg.items():
        name = prefix + key
        if isinstance(value, bool):
            continue
        if isinstance(value, (int, float)):
            out[name] = float(value)
        elif isinstance(value, dict):
            out.update(flatten_metrics(value, name + "."))
    return out


def _threshold_for(leg_name: str, metric_path: str
                   ) -> Optional[Tuple[str, float]]:
    leaf = metric_path.rsplit(".", 1)[-1]
    return PER_LEG_THRESHOLDS.get((leg_name, leaf)) \
        or THRESHOLDS.get(leaf)


def diff_leg(leg_name: str, prev: dict, latest: dict) -> List[dict]:
    """Per-metric comparison rows for one leg present in both records."""
    rows: List[dict] = []
    prev_m = flatten_metrics(prev)
    latest_m = flatten_metrics(latest)
    for path in sorted(set(prev_m) & set(latest_m)):
        p, l = prev_m[path], latest_m[path]
        rule = _threshold_for(leg_name, path)
        row = {"leg": leg_name, "metric": path, "prev": p, "latest": l,
               "status": "untracked", "direction": None,
               "threshold": None, "delta_pct": None}
        if p != 0:
            row["delta_pct"] = round((l - p) / abs(p) * 100.0, 2)
        if rule is None:
            rows.append(row)
            continue
        direction, threshold = rule
        row["direction"] = direction
        row["threshold"] = threshold
        if direction == "lower_abs":
            regressed = l > p + threshold
            improved = l < p - threshold
        elif direction == "higher_abs":
            # absolute points in the good-is-higher direction (e.g. a
            # percentage-improvement column whose base can sit near 0,
            # where a relative threshold would be noise)
            regressed = l < p - threshold
            improved = l > p + threshold
        elif p == 0:
            # no relative base: any appearance of a nonzero value in
            # the bad direction is flagged only for lower-is-better
            # (0 -> N bytes/seconds is growth; 0 -> N tok/s is a fresh
            # measurement, not a regression)
            regressed = direction == "lower" and l > 0
            improved = False
        else:
            change = (l - p) / abs(p)
            if direction == "higher":
                regressed = change < -threshold
                improved = change > threshold
            else:
                regressed = change > threshold
                improved = change < -threshold
        row["status"] = ("regressed" if regressed
                         else "improved" if improved else "ok")
        rows.append(row)
    return rows


def build_report(records: List[dict],
                 notes: Optional[List[str]] = None) -> dict:
    """The full comparison: latest record vs the most recent earlier
    record with the same backend.  ``notes`` carries loader-side
    remarks (skipped lines, collapsed duplicates) into the report."""
    report = {
        "records_seen": len(records),
        "comparable": False,
        "notes": list(notes or ()),
        "latest": None,
        "previous": None,
        "legs": {},
        "regressions": [],
        "improvements": [],
        "structural_violations": [],
    }
    if records:
        # structural refusal gates the LATEST record alone — a record
        # whose timed sub-legs are missing required stamps must fail
        # --check even on a fresh history with nothing to diff
        report["structural_violations"] = validate_structure(
            records[-1])
        for row in report["structural_violations"]:
            report["notes"].append(
                "STRUCTURAL: %s leg refused — %s"
                % (row["leg"], row["reason"]))
    if len(records) < 2:
        report["notes"].append(
            "fewer than 2 parseable records: nothing to diff (a fresh "
            "history passes --check by definition)")
        return report
    latest = records[-1]
    previous = None
    for rec in reversed(records[:-1]):
        if rec.get("backend") == latest.get("backend"):
            previous = rec
            break
    if previous is None:
        report["notes"].append(
            "no earlier record shares the latest record's backend %r: "
            "cross-backend diffs would flag hardware, not code"
            % (latest.get("backend"),))
        return report
    report["comparable"] = True
    for rec, key in ((latest, "latest"), (previous, "previous")):
        report[key] = {"measured_at": rec.get("measured_at"),
                       "git_rev": rec.get("git_rev"),
                       "backend": rec.get("backend"),
                       "source": rec.get("_source")}
    prev_legs = previous.get("legs", {})
    latest_legs = latest.get("legs", {})
    for name in sorted(set(prev_legs) | set(latest_legs)):
        if name not in latest_legs:
            report["notes"].append("leg %r present only in the "
                                   "previous record" % name)
            continue
        if name not in prev_legs:
            report["notes"].append("leg %r is new in the latest "
                                   "record" % name)
            continue
        rows = diff_leg(name, prev_legs[name], latest_legs[name])
        report["legs"][name] = rows
        for row in rows:
            if row["status"] == "regressed":
                report["regressions"].append(row)
            elif row["status"] == "improved":
                report["improvements"].append(row)
    return report


def _fmt_num(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return "%.6g" % v


def render_markdown(report: dict) -> str:
    lines = ["# Bench regression report", ""]
    lines.append("records seen: %d" % report["records_seen"])
    for note in report["notes"]:
        lines.append("- note: %s" % note)
    if not report["comparable"]:
        lines.append("")
        lines.append("**no comparable record pair — nothing gated**")
        return "\n".join(lines) + "\n"
    for key in ("previous", "latest"):
        meta = report[key]
        lines.append("- %s: %s @ %s on %s (%s)"
                     % (key, meta["git_rev"], meta["measured_at"],
                        meta["backend"], meta["source"]))
    lines.append("")
    n_reg = len(report["regressions"])
    n_imp = len(report["improvements"])
    lines.append("**%d regression%s, %d improvement%s**"
                 % (n_reg, "" if n_reg == 1 else "s",
                    n_imp, "" if n_imp == 1 else "s"))
    lines.append("")
    for leg, rows in report["legs"].items():
        flagged = [r for r in rows if r["status"] in ("regressed",
                                                      "improved")]
        ok = sum(1 for r in rows if r["status"] == "ok")
        untracked = sum(1 for r in rows if r["status"] == "untracked")
        lines.append("## %s" % leg)
        lines.append("%d metrics within threshold, %d untracked"
                     % (ok, untracked))
        if flagged:
            lines.append("")
            lines.append("| metric | prev | latest | Δ% | threshold "
                         "| status |")
            lines.append("|---|---|---|---|---|---|")
            for r in sorted(flagged,
                            key=lambda r: (r["status"] != "regressed",
                                           r["metric"])):
                thr = ("±%.0f abs" % r["threshold"]
                       if r["direction"] in ("lower_abs", "higher_abs")
                       else "%s ±%.0f%%" % (r["direction"],
                                            r["threshold"] * 100))
                lines.append("| %s | %s | %s | %s | %s | %s |"
                             % (r["metric"], _fmt_num(r["prev"]),
                                _fmt_num(r["latest"]),
                                _fmt_num(r["delta_pct"]), thr,
                                ("**%s**" % r["status"])
                                if r["status"] == "regressed"
                                else r["status"]))
        lines.append("")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.bench_report",
        description="diff the latest two comparable bench records and "
                    "flag per-leg metric regressions")
    ap.add_argument("--history", default=DEFAULT_HISTORY,
                    help="BENCH_HISTORY.jsonl path")
    ap.add_argument("--rounds", default=DEFAULT_ROUNDS,
                    help="glob of BENCH_r*.json round wrappers "
                         "('' to skip)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when any tracked metric regressed "
                         "(the CI gate)")
    args = ap.parse_args(argv)

    notes: List[str] = []
    records = load_history(args.history, notes=notes)
    if args.rounds:
        records.extend(load_round_files(args.rounds))
    # dedup BEFORE sorting: a round wrapper and the history line it was
    # promoted into describe the SAME run ((measured_at, rev, backend)
    # is the run identity) — pairing them would diff a run against
    # itself and turn the gate into a no-op.  History is loaded first,
    # so the history copy wins; collapses are said out loud, because a
    # history of duplicates leaves NOTHING to gate and the report must
    # not look like it compared something
    seen, unique = set(), []
    for rec in records:
        key = (rec.get("measured_at"), rec.get("git_rev"),
               rec.get("backend"))
        if key in seen:
            notes.append("duplicate record %s (same measured_at/"
                         "git_rev/backend) collapsed"
                         % rec.get("_source", "?"))
            continue
        seen.add(key)
        unique.append(rec)
    records = unique
    # chronological: undated records (some round wrappers) sort first
    # as "oldest known", keeping the dated history authoritative
    records.sort(key=lambda r: r.get("measured_at") or "")
    report = build_report(records, notes=notes)
    rc = 1 if (args.check and (report["regressions"]
                               or report["structural_violations"])) \
        else 0
    if args.json:
        report["exit_code"] = rc
        json.dump(report, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return rc
    sys.stdout.write(render_markdown(report))
    if args.check:
        n_reg = len(report["regressions"])
        n_bad = len(report["structural_violations"])
        sys.stdout.write("--check: %s\n"
                         % ("FAIL (%d regression%s, %d structural)"
                            % (n_reg, "" if n_reg == 1 else "s", n_bad)
                            if rc else "pass"))
    return rc


if __name__ == "__main__":
    sys.exit(main())
