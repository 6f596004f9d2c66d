"""Readings that the limits, the knee and the bounds are set from.  Not
part of a run: the builder of a benchmark PR calls it on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3
        --seconds 20 [--control 1] [--rates 2.0,2.5,3.0]
        [--engine cache_dtype=bfloat16]

In ONE process (set-up is long) it serves or trains each seed through the
cell's own timed path at the cell's own load, compares with the reference
as a run does, and with ``--control 1`` also reads the control: the
reference in the nearest precision below the one the configuration states
(bfloat16 for the float32 serving configuration, fp8 for the bf16 training
ones).  ``--rates`` sweeps an open-loop cell's arrival rate to find the
knee.  One JSON line per reading goes to
``chiprun_out/calibrate_<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run                                     # noqa: E402
from harness import correct, device, traffic as traffic_mod  # noqa: E402
from harness import weights, window                          # noqa: E402


def emit(out, **row) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def serve_cell(args, loaded, jax, out) -> None:
    from harness import serve
    from harness.client import StreamClient

    cfg, tr = loaded["cfg"], loaded["traffic"]
    for pair in filter(None, args.engine.split(",")):
        # the program's own lower-precision path, switched on: the control
        key, _, value = pair.partition("=")
        cfg["engine"][key] = value
    seeds = [int(s) for s in args.seeds.split(",")]
    t0 = time.perf_counter()
    model, engine, front = serve.build(cfg, seeds[0])
    engine.start()
    front.start()
    host, port = front.address
    try:
        serve.warm_up(lambda: StreamClient(host, port), cfg,
                      traffic_mod.Schedule(tr, seeds[0]))
        print("[calibrate] set-up %.1f s" % (time.perf_counter() - t0),
              flush=True)
        sizes = serve.model_sizes(cfg)
        by_name = dict(model.named_parameters())
        rates = [float(r) for r in args.rates.split(",")] if args.rates \
            else [None]
        loaded_seed = seeds[0]
        for seed in seeds:
            for rate in rates:
                if seed != loaded_seed:
                    # drop the old weights before the new ones are made:
                    # two sets and the cache do not fit together
                    engine.refresh_weights()
                    for p in by_name.values():
                        p._replace_value(p.value[..., :1])
                    serve.load_weights(model, cfg, seed)
                    engine.refresh_weights()
                    loaded_seed = seed
                tr_now = dict(tr, rate_per_s=rate) if rate else dict(tr)
                if args.clients:
                    tr_now["clients"] = args.clients
                schedule = traffic_mod.Schedule(tr_now, seed)
                before = serve.engine_counters(engine)
                client = StreamClient(host, port)
                got = serve.drive(client, schedule, cfg, tr_now,
                                  args.seconds)
                serve.drain(client, got["records"], got["t_open"],
                            got["t_close"], tr.get("drain_s", 10.0))
                queue_at_close = engine.queue_depth
                client.abandon()
                while engine.live_requests:
                    time.sleep(0.05)
                after = serve.engine_counters(engine)
                recs, a, b = got["records"], got["t_open"], got["t_close"]
                mid = (a + b) / 2
                row = {"kind": "serve", "seed": seed, "rate": rate,
                       "seconds": args.seconds,
                       "tokens_per_s": window.tokens_in_window(recs, a, b)
                       / (b - a),
                       "compiled": before["compiles"] != after["compiles"],
                       "queue_at_close": queue_at_close,
                       "inflight_at_open": sum(
                           1 for r in recs if r["due"] < a and (
                               r["done"] is None or r["done"] >= a)),
                       "inflight_at_close": sum(
                           1 for r in recs if r["due"] < b and (
                               r["done"] is None or r["done"] >= b))}
                for name, fn in (("ttft", window.ttft_ms),
                                 ("tpot", window.tpot_ms)):
                    for half, (x, y) in (("all", (a, b)), ("h1", (a, mid)),
                                         ("h2", (mid, b))):
                        v = fn(recs, x, y, 1e6)
                        if v:
                            row["%s_mean_%s" % (name, half)] = \
                                sum(v) / len(v)
                            row["%s_tail5_%s" % (name, half)] = \
                                serve._tail_mean(v, 0.2)
                            row["%s_p50_%s" % (name, half)] = \
                                window.percentile(v, 0.5)
                            row["%s_p90_%s" % (name, half)] = \
                                window.percentile(v, 0.9)
                            row["%s_n_%s" % (name, half)] = len(v)
                if args.check:
                    sample = correct.sample_finished(
                        recs, seed, args.check_requests
                        or tr["check_requests"])
                    w = weights.from_program(
                        sizes, lambda n: by_name[n].value)
                    controls = tuple(args.controls.split(",")) \
                        if args.control else ()
                    g = correct.served_gaps(cfg, seed, schedule, sample,
                                            w=w, controls=controls)
                    del w
                    storage = device.storage_census(jax, cfg["storage"])
                    held = correct.serving_numbers(
                        g["gaps"], g["agree"], storage, cfg["limits"])
                    row.update(gap_max=max(g["gaps"]),
                               gap_mean=sum(g["gaps"]) / len(g["gaps"]),
                               tokens_checked=len(g["gaps"]),
                               agree=g["agree"] / len(g["gaps"]),
                               storage=storage,
                               correct=correct.verdict(held),
                               not_held=[k for k, v in held.items()
                                         if not v["ok"]])
                    for mode, c in g["control_gaps"].items():
                        # the reference in a lower precision, in the
                        # program's place, through the same verdict; it
                        # holds its weights as the program does, so its
                        # storage is the program's
                        low = correct.serving_numbers(
                            c, 0, storage, cfg["limits"])
                        row["control_%s_gap_max" % mode] = max(c)
                        row["control_%s_gap_mean" % mode] = sum(c) / len(c)
                        row["control_%s_correct" % mode] = \
                            correct.verdict(low)
                    if args.dump_gaps:
                        row.update(gaps=g["gaps"],
                                   control_gaps=g["control_gaps"])
                emit(out, **row)
    finally:
        front.shutdown()
        engine.shutdown(drain=False)
    print("[memory] " + json.dumps(device.memory(jax, 1)), flush=True)


def train_cell(args, loaded, jax, out) -> None:
    from harness import train

    cfg, tr = loaded["cfg"], loaded["traffic"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = {"cfg": cfg, "traffic": tr, "seed": seed,
               "chips": loaded["cell"]["chips"],
               "seconds": args.seconds, "trace": False, "trace_dir": None}
        t0 = time.perf_counter()
        got = train.measure(run, jax)
        rate = got["steps"] * got["tokens_per_step"] \
            / (got["t_close"] - got["t_open"])
        mem = got["memory"]
        got["free"]()
        row = {"kind": "train", "seed": seed, "tokens_per_s": rate,
               "setup_s": got["t_open"] - t0, "losses": got["losses"],
               "memory_peak": device.memory_peak_bytes(mem)}
        if args.check:
            t1 = time.perf_counter()
            ref = correct.reference_training(cfg, tr, seed,
                                             got["host_batches"])
            row["reference_s"] = time.perf_counter() - t1
            row.update(_train_numbers("", (got["losses"], got["first_grad"],
                                           got["change"]), ref))
            if args.control:
                low = correct.reference_training(
                    cfg, tr, seed, got["host_batches"], mode="fp8")
                row.update(_train_numbers("control_", low, ref))
        emit(out, **row)


def _train_numbers(prefix: str, side, ref) -> dict:
    losses, first, change = side
    g, _, g_med = correct.worst_leaf(first, ref[1])
    c, _, c_med = correct.worst_leaf(change, ref[2])
    return {prefix + "loss_rel": [abs(p - r) / abs(r)
                                  for p, r in zip(losses, ref[0])],
            prefix + "first_grad_worst": g, prefix + "first_grad_median":
            g_med, prefix + "change_worst": c, prefix + "change_median":
            c_med}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--controls", default="bfloat16",
                    help="serving: the reference's lower precisions")
    ap.add_argument("--engine", default="",
                    help="serving: engine options to override, k=v,k=v; "
                         "cache_dtype=bfloat16 is the program's own "
                         "lower-precision path, the control")
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--check-requests", type=int, default=0)
    ap.add_argument("--clients", type=int, default=0)
    ap.add_argument("--dump-gaps", type=int, default=0)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--any-device", type=int, default=0)
    args = ap.parse_args(argv)
    loaded = bench_run.load_cell(args.workload, args.manifest)

    import jax

    if not args.any_device:
        device.require_chips(jax, loaded["cell"]["chips"])
    device.use_compile_cache(jax, ROOT)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        "calibrate_%s.jsonl" % args.workload)
    with open(path, "a") as out:
        cell = serve_cell if loaded["cfg"]["driver"] == "serve" \
            else train_cell
        cell(args, loaded, jax, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
