"""The latent decode kernel alone, on the chip: time a call at the
``axk1-batch-closed`` cell's geometry (32 rows of 64 heads against 2,305
blocks of 128 positions x 640 bfloat16 values, 72 table entries a row,
one layer) at contexts 1,400 / 4,900 / 9,000 and at the cell's mix of
them, for each score-tile budget asked for (docs/DESIGN.md section 5w;
the table in PERF.md section 6, PR 41, is this script's).

    python3 tools/latent_kernel_bench.py                  # the rule as it is
    python3 tools/latent_kernel_bench.py --budgets 8192 32768 65536 \
        --lq 1 2 8                                        # widths 1, 4, 8
    python3 tools/latent_kernel_bench.py --tree _checkout/parent

``--budgets`` sets ``ops.pallas_decode._LATENT_SCORE_TILE`` for the call
(a tree without that name runs its kernel as it is).  One JSON line a
point.  A time comes from a TPU only: anywhere else the script stops,
unless ``--cpu-toy`` asks for a rehearsal of its control flow at toy
sizes under the interpreter, which prints no time.
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


def _time_ms(fn, args, n):
    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / n * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--budgets", type=int, nargs="*", default=[],
                    help="score-tile budgets to try (default: the rule's)")
    ap.add_argument("--lq", type=int, nargs="*", default=[1])
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--cpu-toy", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.tree))
    from tools.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    pd = importlib.import_module("paddle_tpu.ops.pallas_decode")
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.cpu_toy:
        print("latent_kernel_bench: no TPU (%s); a time comes from the chip "
              "alone" % platform, file=sys.stderr)
        return 1
    if args.cpu_toy:
        b, h, width, r, bs, mb, dtype = 4, 4, 256, 128, 8, 16, jnp.float32
        contexts = {"30": [30] * b, "120": [120] * b,
                    "mix": [9, 40, 77, 120]}
    else:
        b, h, width, r, bs, mb, dtype = 32, 64, 640, 512, 128, 72, \
            jnp.bfloat16
        contexts = {str(c): [c] * b for c in (1400, 4900, 9000)}
        # the cell's rows end anywhere: evenly spaced over its contexts
        contexts["mix"] = [int(c) for c in np.linspace(1400, 9000, b)]
    nb = 1 + b * mb
    key = jax.random.PRNGKey(0)
    pool = (jax.random.normal(key, (nb, bs, width), jnp.float32) * 0.5) \
        .astype(dtype)
    table = jnp.asarray(1 + np.arange(b * mb).reshape(b, mb), jnp.int32)

    def run(route):
        return jax.jit(lambda q, c, t, p: fa.latent_decode_attention(
            q, c, t, p, r, 0.1, route=route))

    device = jax.devices()[0]
    plain = run("composition")
    for lq in args.lq:
        q = (jax.random.normal(jax.random.fold_in(key, lq),
                               (b, h, lq, width), jnp.float32) * 0.5) \
            .astype(dtype)
        q_pos = {name: jnp.asarray(
            [[c - lq + 1 + t for t in range(lq)] for c in ctx], jnp.int32)
            for name, ctx in contexts.items()}
        want = {name: plain(q, pool, table, q_pos[name]).astype(jnp.float32)
                for name in contexts}
        for budget in args.budgets or [None]:
            if budget is not None and hasattr(pd, "_LATENT_SCORE_TILE"):
                pd._LATENT_SCORE_TILE = budget
                pd._latent_call.clear_cache()
            try:
                split = pd.latent_sub_blocks(mb, h * lq, bs)
            except TypeError:             # a tree before the tile's rule
                split = (pd.latent_sub_blocks(mb), 1)
            kernel = run("pallas")
            for name, ctx in contexts.items():
                got = kernel(q, pool, table, q_pos[name])
                live = sum(c // bs + 1 for c in ctx)
                line = {
                    "tree": os.path.relpath(os.path.abspath(args.tree), ROOT),
                    "device": "%s %s" % (platform, device.device_kind),
                    "rows": h * lq, "lq": lq, "context": name,
                    "budget": getattr(pd, "_LATENT_SCORE_TILE", None),
                    "sub": split[0], "tile": split[1], "live_blocks": live,
                    "max_abs_diff": float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want[name]))),
                    "max_abs": float(jnp.max(jnp.abs(want[name])))}
                if platform == "tpu":
                    ms = _time_ms(kernel, (q, pool, table, q_pos[name]),
                                  args.calls)
                    least = sum(c + 1 for c in ctx) * width \
                        * pool.dtype.itemsize / HBM_BYTES_PER_S * 1e3
                    line.update(kernel_ms=ms, us_a_live_block=ms * 1e3 / live,
                                read_ms=least, read_share=least / ms)
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
