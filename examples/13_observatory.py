"""Example 13: the serving observatory (docs/DESIGN.md §5h).

Example 12 showed WHERE the time went; this one shows what the
HARDWARE was asked to do and whether the engine KEPT ITS PROMISES:

1. **cost/memory attribution**: every decode executable compiles
   through the AOT path (``jit.aot``), so ``engine.cost_report()``
   carries XLA's own cost/memory analyses — FLOPs and bytes-accessed
   of one batched step, the HBM the executable reserves, and the cache
   footprint that reconciles exactly with the allocator's
   ``kv_reachable_bytes`` accounting.  Surfaced as the
   ``serving_step_*`` gauges on ``GET /metrics``;
2. **SLO burn-rate tracking** (``serving/slo.py``): declarative
   objectives (TTFT p95, availability) over rolling tick windows with
   the fast/slow multi-window alert pairing — a seeded chaos burst
   flips the availability alert, clean traffic clears it, and
   ``GET /slo`` / ``health()`` carry the state throughout;
3. **structured logs** (``serving/log.py``): one JSON line per
   admission / terminal / recovery / shed / SLO flip, correlated with
   trace tick numbers — a no-op when unconfigured.

What a deployment's speed IS comes from none of these: that is the
benchmark's (``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds 45 --trace <0|1>``, on a TPU; cells in ``BENCHMARK.json``).

Run: python examples/13_observatory.py [--tokens 8]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import argparse
import io
import json

import numpy as np

import paddle_tpu as pt
from paddle_tpu.models import TransformerLM
from paddle_tpu.serving import (Objective, ServingEngine, SLOTracker,
                                faults)
from paddle_tpu.serving import log as slog


def drain(engine):
    while engine.pump(4):
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8)
    args = ap.parse_args()

    pt.seed(0)
    model = TransformerLM(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128,
                          max_position=128, causal=True, dropout=0.0)
    tracker = SLOTracker(
        [Objective("availability", "availability", 0.5),
         Objective("ttft_p95", "ttft", 0.95, threshold_s=30.0)],
        fast_window=3, slow_window=12)
    engine = ServingEngine(model, max_len=128, slots=2,
                           buckets=[64, 128], slo=tracker,
                           max_retries=0)
    rng = np.random.RandomState(0)
    log_buf = io.StringIO()

    with slog.logging_to(log_buf):
        print("== 1. clean traffic, cost attribution off the artifact")
        for i in range(3):
            engine.submit(rng.randint(0, 256, (40,)).astype("int32"),
                          args.tokens, request_id="warm-%d" % i)
        drain(engine)
        rep = engine.cost_report()
        d = rep["derived"]
        print("   decode step: %.3g FLOPs, %.3g bytes accessed, "
              "%d B HBM reserved"
              % (d["step_flops"], d["step_bytes_accessed"],
                 d["hbm_reserved_bytes"]))
        print("   per token: %.3g FLOPs, %.3g bytes (over %d slots)"
              % (d["flops_per_token"], d["bytes_per_token"],
                 engine._pool.slots))
        stats = engine.cache_stats()
        assert d["kv_cache_bytes"] == stats["pool_bytes"]
        print("   cache footprint: compiler %d B == allocator %d B "
              "(reconciled)" % (d["kv_cache_bytes"],
                                stats["pool_bytes"]))

        print("== 2. seeded chaos: the availability alert flips")
        plane = faults.FaultPlane(chaos_seed=11, chaos_p=1.0,
                                  chaos_points=("pool.step",),
                                  max_faults=2)
        with faults.injected(plane):
            for wave in range(2):
                for i in range(2):
                    engine.submit(
                        rng.randint(0, 256, (20,)).astype("int32"),
                        args.tokens, request_id="c%d-%d" % (wave, i))
                drain(engine)
        snap = engine.slo_snapshot()
        avail = [o for o in snap["objectives"]
                 if o["name"] == "availability"][0]
        print("   injected %d faults -> alert_active=%s "
              "(fast burn %.2f, slow burn %.2f)"
              % (plane.fault_count, avail["alert_active"],
                 avail["fast_burn_rate"], avail["slow_burn_rate"]))
        assert avail["alert_active"]
        print("   health() says: %s" % engine.health()["slo"])

        print("== 3. recovery: clean traffic clears the alert")
        for i in range(6):
            engine.submit(rng.randint(0, 256, (20,)).astype("int32"),
                          2, request_id="r-%d" % i)
            drain(engine)
        avail = [o for o in engine.slo_snapshot()["objectives"]
                 if o["name"] == "availability"][0]
        print("   alert_active=%s after %d clean requests"
              % (avail["alert_active"], 6))
        assert not avail["alert_active"]

    print("== 4. the structured log saw every edge")
    lines = [json.loads(l) for l in log_buf.getvalue().splitlines()]
    events = {}
    for rec in lines:
        events[rec["event"]] = events.get(rec["event"], 0) + 1
    for name in sorted(events):
        print("   %-18s x%d" % (name, events[name]))
    assert events.get("slo.alert") and events.get("slo.alert_cleared")

    print("== 5. SLO gauges ride the prometheus scrape")
    scrape = engine.metrics.render_prometheus()
    for line in scrape.splitlines():
        if line.startswith("serving_slo_availability") or \
                line.startswith("serving_step_"):
            print("   " + line)
    print("ok")


if __name__ == "__main__":
    main()
