"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

Everything about a cell comes from data: ``BENCHMARK.json`` names the
configuration, the traffic mix and the per-layer metrics; their files are
found by name under ``benchmark/configs``, ``benchmark/traffic``,
``benchmark/end_to_end``, ``benchmark/metrics`` and ``benchmark/readers``.
The last line of standard output is the result, one JSON object.  See
PERF.md, "Adding a cell".
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_age_s() -> float:
    """Seconds since this process started, so that ``setup_s`` runs from
    process start and not from the first line of this file."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_T0 = process_age_s()


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, manifest_path: str = None) -> dict:
    """The cell, its configuration, its traffic and the metrics it reports,
    all found by name."""
    manifest = load_json(manifest_path or os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit("unknown workload %r; BENCHMARK.json has %s"
                         % (name, sorted(cells)))
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return {
        "cell": cell, "manifest": manifest,
        "cfg": load_json(ROOT, conf["file"]),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in manifest["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def read_metrics(entries: list, folder: str, ctx: dict) -> dict:
    """Each metric through the reader its own file names
    (``benchmark/<folder>/<name>.json``); a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in entries:
        spec = load_json(HERE, folder, m["name"] + ".json")
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, require_chip: bool = True, hooks: dict = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    hooks = hooks or {}
    for p in (HERE, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import correct, device

    loaded = load_cell(args.workload, hooks.get("manifest"))
    cell, cfg, traffic = loaded["cell"], loaded["cfg"], loaded["traffic"]

    import jax

    if require_chip:
        dev = device.require_chips(jax, cell["chips"])
    else:           # benchmark/tests only: the look for a chip is skipped
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": cell["chips"]}
    cache_dir = device.use_compile_cache(jax, ROOT)
    cache = device.CacheCounter(jax)
    say("[device] platform=%s kind=%s count=%d of %d; compile cache %s"
        % (dev["platform"], dev["kind"], dev["count"], len(jax.devices()),
           cache_dir))

    trace_dir = os.path.join(HERE, "_trace", args.workload)
    run = {"cfg": cfg, "traffic": traffic, "seed": args.seed,
           "seconds": args.seconds, "trace": bool(args.trace),
           "trace_dir": trace_dir, "chips": cell["chips"]}
    if args.trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    driver = importlib.import_module("harness." + cfg["driver"])
    got = driver.measure(run, jax, **hooks.get("measure", {}))

    setup_s = got["t_open"] - _T0 + _AGE_AT_T0
    parts = dict(got["setup_parts"],
                 before_main_s=_AGE_AT_T0, total_setup_s=setup_s)
    say("[setup] " + json.dumps(parts))
    say("[cache] %d hit(s), %d miss(es)" % (cache.hits, cache.misses))
    mem = got["memory"]
    say("[memory] " + json.dumps(mem))

    summary = driver.summarize(run, got)      # prints counts, may refuse
    from harness import readctx
    ctx = readctx.base(run, got, summary, dev)
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    result_device = dict(dev, memory_peak_bytes=device.memory_peak_bytes(mem))
    extra = {}
    if args.trace:
        ctx = readctx.traced(ctx, run, got)
        metrics = read_metrics(loaded["per_layer"], "metrics", ctx)
        result_device.update(busy_s=ctx["busy_s"], window_s=ctx["window_s"])
        extra["breakdown"] = ctx["breakdown"]
    else:
        wanted = [m for m in loaded["end_to_end"] if m["name"] != "setup_s"]
        metrics.update(read_metrics(wanted, "end_to_end", ctx))
        lacking = [m["name"] for m in wanted if m["name"] not in metrics]
        if lacking:
            raise SystemExit("benchmark: the window gave nothing to read %s "
                             "from" % lacking)
        # the per-layer metrics that need no tracer, read where nothing
        # traces; the driver does not read this key
        extra["per_layer_host_clock"] = read_metrics(
            [m for m in loaded["per_layer"] if m["source"] == "host_clock"],
            "metrics", ctx)
        say("[host_clock] " + json.dumps(
            {k: v["value"] for k, v in extra["per_layer_host_clock"].items()}))

    # the reference runs last: the window is closed, the peak is read and
    # the program's state is freed
    got["free"]()
    t_ref = time.perf_counter()
    compared = driver.compare(run, got)
    say("[reference] %.1f s, not counted in setup_s"
        % (time.perf_counter() - t_ref))
    ok = correct.verdict(compared)
    result = {"correct": ok, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics,
              "device": result_device}
    result.update(extra)
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in compared.items()}
    sys.stdout.flush()
    correct.report(compared)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
