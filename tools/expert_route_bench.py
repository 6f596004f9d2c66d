"""One serving expert layer alone, on the chip: time every route of
``F.sparse_experts`` at the geometry of each benchmark cell that has
expert layers (docs/DESIGN.md section 5w; the table in PERF.md section 6,
PR 45, is this script's).

    python3 tools/expert_route_bench.py                   # every geometry
    python3 tools/expert_route_bench.py --geometry axk1 --touched draw all
    python3 tools/expert_route_bench.py --tree _checkout/parent

| geometry | a call | the cell |
| --- | --- | --- |
| ``axk1`` | 32 rows, 12 of 192 experts of 7168 x 2048 held, 8 a token | ``axk1-batch-closed``, a decode step |
| ``zaya`` | 64 rows, 16 of 16 experts of 2048 x 2048, 1 a token | ``zaya8b-batch-closed``, a decode step |
| ``sdar`` | 256 rows (32 slots x two blocks of 4), 128 of 128 experts of 2048 x 768, 8 a token | ``sdar30b-batch-closed``, a block step |

Each route the tree has (``every``: every held expert on every row;
``touched``: a loop over the experts some row chose; ``grouped``: the
pairs sorted through grouped matmuls) is called with its pairs' keys
GIVEN, so the count of touched experts is forced: ``one``, a ``quarter``,
a ``half`` and ``all`` of the held experts, and ``draw``, the count the
cell's own routing gives (as many experts as ``rows`` even choices of
``top_k`` of all the experts touch, rounded).  The pairs held are as many
as the cell's routing sends this share (``rows x top_k x held / all``, at
least one a touched expert), dealt round the touched experts in turn;
with fewer touched experts than ``top_k`` a row holds one twice, which
changes no route's work.  ``--calls`` layers are chained in one program,
each on the one before's output brought back to unit mean square, as a
model's layers are.  One JSON line a point: ms a call, the touched experts'
bytes over 819 GB/s in ms and as a share of the call, the widest difference
from ``every``; at ``half`` also ``skip_cost_us``, what a turn of the
touched route's loop costs over an expert's share of ``every`` (from
``one`` to ``half``; the constant ``_SKIP_COST_S`` of
``nn/functional/moe.py`` is read off it; at ``all`` the touched route runs
``every`` behind a branch), and on every line the route the tree's rule
picks at the geometry.  A time comes from a TPU only: anywhere else the script stops,
unless ``--cpu-toy`` asks for a rehearsal of its control flow at toy sizes,
which prints no time.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES_PER_S = 819e9           # TPU v5e, as benchmark/harness has it

# name: rows, experts held, experts in all, top_k, width, an expert's size
GEOMETRIES = {
    "axk1": (32, 12, 192, 8, 7168, 2048),
    "zaya": (64, 16, 16, 1, 2048, 2048),
    "sdar": (256, 128, 128, 8, 2048, 768),
}
TOY = (8, 4, 16, 2, 32, 16)
ROUTES = {"every": "_every_expert", "touched": "_touched",
          "grouped": "_grouped"}


def touched_counts(rows: int, held: int, experts: int, top_k: int) -> dict:
    """``{label: experts touched}``."""
    draw = held * (1.0 - (1.0 - top_k / experts) ** rows)
    return {"one": 1, "quarter": max(1, held // 4), "half": max(1, held // 2),
            "draw": max(1, round(draw)), "all": held}


def keys_for(touched: int, rows: int, held: int, experts: int, top_k: int):
    """The pairs' keys, token-major: ``held`` (an expert held elsewhere)
    but for the pairs this share holds, dealt round the first ``touched``
    experts in turn."""
    import numpy as np

    pairs = min(rows * top_k, max(touched, rows * top_k * held // experts))
    key = np.full(rows * top_k, held, np.int32)
    at = np.linspace(0, rows * top_k - 1, pairs).astype(np.int64)
    key[at] = np.arange(pairs) % touched
    return key


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--geometry", nargs="*", default=list(GEOMETRIES))
    ap.add_argument("--route", nargs="*", default=list(ROUTES))
    ap.add_argument("--touched", nargs="*", default=[],
                    help="only these counts (default: all five)")
    ap.add_argument("--calls", type=int, default=12,
                    help="layers chained in one program")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--cpu-toy", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.tree))
    from tools.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    moe = importlib.import_module("paddle_tpu.nn.functional.moe")
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu_toy:
        print("expert_route_bench: no TPU (%s); a time comes from the chip "
              "alone" % device.platform, file=sys.stderr)
        return 1
    calls = 2 if args.cpu_toy else args.calls
    dtype = jnp.float32 if args.cpu_toy else jnp.bfloat16

    def chain(route, held, top_k):
        def run(x, gates, key, *weights):
            for _ in range(calls):
                y = x.astype(jnp.float32) \
                    + route(x, gates, key, held, top_k, *weights)
                # rows of unit mean square again: a chain keeps its size
                x = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True))
                     ).astype(x.dtype)
            return x
        return jax.jit(run)

    def ms_a_call(fn, operands):
        jax.block_until_ready(fn(*operands))
        start = time.perf_counter()
        for _ in range(args.repeats):
            out = fn(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / (args.repeats * calls) * 1e3

    for name in args.geometry:
        rows, held, experts, top_k, width, size = \
            TOY if args.cpu_toy else GEOMETRIES[name]
        kx, kg, ku, kd = jax.random.split(jax.random.PRNGKey(len(name)), 4)
        x = jax.random.normal(kx, (rows, width), jnp.float32).astype(dtype)
        weights = tuple(
            (jax.random.normal(k_, s, jnp.float32) * 0.02).astype(dtype)
            for k_, s in ((kg, (held, width, size)), (ku, (held, width, size)),
                          (kd, (held, size, width))))
        gates = jnp.full((rows, top_k), 1.0 / top_k, jnp.float32)
        expert_bytes = 3 * width * size * jnp.dtype(dtype).itemsize
        rule = moe.expert_route(rows, held, experts, top_k, width, size,
                                jnp.dtype(dtype).itemsize) \
            if hasattr(moe, "expert_route") else None
        fns = {r: chain(getattr(moe, ROUTES[r]), held, top_k)
               for r in args.route if hasattr(moe, ROUTES[r])}
        seen = {}
        for label, touched in touched_counts(rows, held, experts,
                                             top_k).items():
            if args.touched and label not in args.touched:
                continue
            key = jnp.asarray(keys_for(touched, rows, held, experts, top_k))
            operands = (x, gates, key) + weights
            want = np.asarray(fns["every"](*operands), np.float32) \
                if "every" in fns else None
            for route, fn in fns.items():
                line = {
                    "tree": os.path.relpath(os.path.abspath(args.tree), ROOT),
                    "device": "%s %s" % (device.platform, device.device_kind),
                    "geometry": "toy" if args.cpu_toy else name,
                    "route": route, "rule_picks": rule, "forced": label,
                    "touched": touched, "held": held, "rows": rows,
                    "calls": calls}
                got = np.asarray(fn(*operands), np.float32)
                if want is not None:
                    line.update(
                        max_abs_diff=float(np.abs(got - want).max()),
                        max_abs=float(np.abs(want).max()))
                if device.platform == "tpu":
                    least = touched * expert_bytes / HBM_BYTES_PER_S * 1e3
                    ms = ms_a_call(fn, operands)
                    line.update(ms_a_call=ms, touched_bytes_ms=least,
                                touched_bytes_share=least / ms)
                    seen[route, label] = ms
                    if route == "touched" and label == "half" and touched > 1 \
                            and {("touched", "one"), ("every", "half")} \
                            <= set(seen):
                        line["skip_cost_us"] = 1e3 * (
                            (ms - seen["touched", "one"]) / (touched - 1)
                            - seen["every", "half"] / held)
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
