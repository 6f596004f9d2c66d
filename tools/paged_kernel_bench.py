"""The paged decode kernel alone, on the chip: time ``_paged_call`` at the
geometry of each benchmark cell that runs it (docs/DESIGN.md section 5l;
the table in PERF.md section 6, PR 43, is this script's).

    python3 tools/paged_kernel_bench.py                   # every geometry
    python3 tools/paged_kernel_bench.py --geometry zaya gpt --entries 4 8
    python3 tools/paged_kernel_bench.py --tree _checkout/parent

| geometry | a call | the cell |
| --- | --- | --- |
| ``zaya`` | bfloat16, 2 K/V heads x group 4, blocks of 128, 24 entries, 64 rows | ``zaya8b-batch-closed`` |
| ``gpt`` | float32, 16 heads, blocks of 32, 32 entries, 16 rows | ``gpt1p3b-batch-closed``; its ``chat`` context is ``gpt1p3b-chat-r60``'s |
| ``jamba`` | bfloat16, 1 K/V head x group 20, blocks of 128, 18 entries, 64 rows | ``jamba3b-batch-closed`` |
| ``sdar`` | bfloat16, 4 K/V heads x group 8, 8 positions (the block step's two blocks of 4), blocks of 128, 20 entries, 32 rows | ``sdar30b-batch-closed`` |
| ``ouro`` | bfloat16, 16 heads, blocks of 64, 5 entries, 16 rows, FOUR planes of heads an entry, the chained calls attending plane 0, 1, 2, 3, 0 ... by a head offset | ``ouro2p6b-batch-closed`` |
| ``ouro-blocks`` | the same calls with the planes laid as further BLOCKS (``table + r * num_blocks`` on a pool of four times the blocks, the unplaned kernel) | off the benchmark: the layout not chosen (docs/DESIGN.md, "a stack run several times") |
| ``smallthinker`` | bfloat16, 4 K/V heads x group 7, blocks of 128, 128 entries, 16 rows: a GLOBAL layer's call; with ``--window`` a WINDOW layer's: the table a ring of 4,096 / 128 + 1 = 33 entries, the walk from the band's first entry to its last | ``smallthinker21b-batch-closed`` |
| ``gpt-int8`` | ``gpt``'s call on an int8 pool with its float32 scales (blocks of 32) | off the benchmark: ``cache_dtype="int8"`` at the default block |
| ``gpt-d64`` | ``gpt``'s call at 32 heads of 64 | off the benchmark: a head of half a lane tile, which the walk refuses |

Every row's context at a third, a half and the whole of the table, then
the cell's own mix (evenly spaced over the contexts its traffic holds);
for ``gpt`` also ``chat`` (one live row, fifteen slots as the pool shows
an inactive one: position 0 on the scratch block) and ``empty`` (the
fifteen see nothing: ``q_pos`` -1).  ``--calls`` kernels are chained in
one program, each on the one before's output, as a model's layers are.
``--entries`` caps ``ops.pallas_decode._PAGED_TILE_ENTRIES`` for the run
and ``--kv-mib`` sets ``_KV_VMEM_BUDGET`` (a tree without the first name
runs its kernel as it is).  ``--composition`` times the XLA composition
at each point too (``composition_ms_a_call``): what a geometry the kernel
refuses falls back to; a refused point prints ``refused`` and that time.
One JSON line a
point: ms a call, us a live entry, and the time the live entries' bytes
take at 819 GB/s as a share of the call's.  A time comes from a TPU
only: anywhere else the script stops, unless ``--cpu-toy`` asks for a
rehearsal of its control flow at toy sizes under the interpreter, which
prints no time.

``--write`` times the K/V WRITE alone instead (``ops.flash_attention.
paged_kv_write``: a layer's K and V rows into their donated pools, the
``--calls`` writes chained on the pools as a model's layers are not, so
each waits for the one before): the two scatters (``scatter_ms``) against
the one kernel (``kernel_ms``; ``refused`` or ``scatter by design`` where
it does not run; a tree without the kernel, ``--tree _checkout/parent``,
times its scatters alone), each beside the bytes it moves at 819 GB/s
(the scatter the new rows once, the kernel the rows' groups there and
back) as a share of its time, at the cell's mix of positions and for
``gpt`` at ``chat`` (fifteen inactive slots on the scratch block at one
offset).  PERF.md section 6, PR 48, has its table.
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

# name: rows, query heads, K/V heads, positions, block, head size, table
# entries, type, the (low, high) contexts of the cell's mix
GEOMETRIES = {
    "zaya": (64, 8, 2, 1, 128, 128, 24, "bfloat16", (140, 2500)),
    "gpt": (16, 16, 16, 1, 32, 128, 32, "float32", (150, 560)),
    "jamba": (64, 20, 1, 1, 128, 128, 18, "bfloat16", (140, 1900)),
    "sdar": (32, 32, 4, 8, 128, 128, 20, "bfloat16", (400, 2300)),
    "ouro": (16, 16, 16, 1, 64, 128, 5, "bfloat16", (100, 315)),
    "ouro-blocks": (16, 16, 16, 1, 64, 128, 5, "bfloat16", (100, 315)),
    "smallthinker": (16, 28, 4, 1, 128, 128, 128, "bfloat16", (1500, 13000)),
    "gpt-int8": (16, 16, 16, 1, 32, 128, 32, "int8", (150, 560)),
    "gpt-d64": (16, 32, 32, 1, 32, 64, 32, "float32", (150, 560)),
}
TOY = (4, 4, 2, 2, 8, 16, 6, "float32", (5, 40))
# geometries with window layers: the band's width (``--window`` times the
# windowed call against a ring; the toy's band is 16)
WINDOWS = {"smallthinker": 4096}
# geometries whose pool holds several K/V planes: how many, and how laid
PLANES = {"ouro": (4, "heads"), "ouro-blocks": (4, "blocks")}


def contexts_of(name: str, b: int, lq: int, mb: int, bs: int, mix) -> dict:
    """``{label: [positions a row holds]}``; 0 positions = a row that sees
    nothing."""
    import numpy as np

    whole = mb * bs
    out = {"third": [whole // 3] * b, "half": [whole // 2] * b,
           "whole": [whole] * b,
           "mix": [int(c) for c in np.linspace(mix[0], mix[1], b)]}
    if name.startswith("gpt"):
        out["chat"] = [min(300, whole)] + [lq] * (b - 1)
        out["empty"] = [min(300, whole)] + [0] * (b - 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--geometry", nargs="*", default=list(GEOMETRIES))
    ap.add_argument("--context", nargs="*", default=[],
                    help="only these contexts (default: all)")
    ap.add_argument("--composition", action="store_true",
                    help="time the XLA composition at each point too")
    ap.add_argument("--entries", type=int, nargs="*", default=[],
                    help="caps on a tile's entries to try (default: the "
                         "rule's)")
    ap.add_argument("--kv-mib", type=int, default=None,
                    help="ops.pallas_decode._KV_VMEM_BUDGET for the run, MiB")
    ap.add_argument("--calls", type=int, default=20,
                    help="kernels chained in one program")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--cpu-toy", action="store_true")
    ap.add_argument("--write", action="store_true",
                    help="time the K/V write alone, scatter against kernel")
    ap.add_argument("--window", action="store_true",
                    help="time the WINDOWED call of the geometries that "
                         "have a window: a ring of window / block + 1 "
                         "entries a row")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.tree))
    from tools.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    pd = importlib.import_module("paddle_tpu.ops.pallas_decode")
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu_toy:
        print("paged_kernel_bench: no TPU (%s); a time comes from the chip "
              "alone" % device.platform, file=sys.stderr)
        return 1
    calls = 2 if args.cpu_toy else args.calls
    if args.kv_mib is not None and hasattr(pd, "_PAGED_TILE_ENTRIES"):
        pd._KV_VMEM_BUDGET = args.kv_mib * 1024 * 1024

    def write_chain(route, planes=1, laid=None, hkv=None, nb=None,
                    window=None):
        """``calls`` writes of K and V rows, each into the pools the one
        before returned (donated: updated where they lie)."""
        write = getattr(fa, "paged_kv_write", None)

        def run(k, v, k_new, v_new, phys, off):
            for i in range(calls):
                r = i % planes
                at = {"head_base": jnp.int32(r * hkv)} \
                    if laid == "heads" else {}
                where = phys + r * nb if laid == "blocks" else phys
                if write is None:   # a tree from before the kernel
                    k, v = (fa.paged_cache_write(p, x, where, off, **at)
                            for p, x in ((k, k_new), (v, v_new)))
                else:
                    k, v = write(k, v, k_new, v_new, where, off,
                                 route=route, **at)
            return k, v
        return jax.jit(run, donate_argnums=(0, 1))

    def write_ms(fn, pools, rest):
        pools = fn(*pools, *rest)
        jax.block_until_ready(pools)
        start = time.perf_counter()
        for _ in range(args.repeats):
            pools = fn(*pools, *rest)
        jax.block_until_ready(pools)
        return pools, \
            (time.perf_counter() - start) / (args.repeats * calls) * 1e3

    def chain(route, planes=1, laid=None, hkv=None, nb=None, window=None):
        def run(q, k, v, table, q_pos, *scales):
            for i in range(calls):
                r = i % planes
                at = {"head_base": r * hkv, "plane_heads": hkv} \
                    if laid == "heads" else {}
                if window is not None:
                    at["window"] = window
                q = fa.paged_decode_attention(
                    q, k, v, table + r * nb if laid == "blocks" else table,
                    q_pos=q_pos, route=route, **at,
                    **dict(zip(("k_scale", "v_scale"), scales)))
            return q
        return jax.jit(run)

    def ms_a_call(fn, operands):
        jax.block_until_ready(fn(*operands))
        start = time.perf_counter()
        for _ in range(args.repeats):
            out = fn(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / (args.repeats * calls) * 1e3

    for name in args.geometry:
        b, hq, hkv, lq, bs, d, mb, dtype, mix = \
            TOY if args.cpu_toy else GEOMETRIES[name]
        if GEOMETRIES[name][7] == "int8":
            # the toy keeps a pool's kind (an int8 pool takes no group)
            dtype, hq = "int8", hkv
        dtype = jnp.dtype(dtype)
        quant = dtype == jnp.int8
        window = None
        if args.window:
            if name not in WINDOWS:
                continue
            window = 16 if args.cpu_toy else WINDOWS[name]
        # the contexts are the cell's whatever the table: a ring wraps
        span = mb
        if window is not None:
            mb = window // bs + 1
        nb = 1 + b * mb
        planes, laid = PLANES.get(name, (1, None))
        # the pool's blocks and heads with the planes in them
        pnb = nb * planes if laid == "blocks" else nb
        phkv = hkv * planes if laid == "heads" else hkv
        how = dict(planes=planes, laid=laid, hkv=hkv, nb=nb)
        if window is not None:
            how["window"] = window
        key = jax.random.PRNGKey(len(name))
        kq, kk, kv, ks = jax.random.split(key, 4)
        q = jax.random.normal(kq, (b, hq, lq, d), jnp.float32)
        scales = ()
        if quant:
            # an int8 pool: whole values and a float32 scale a position
            k_pool, v_pool = (jax.random.randint(
                k_, (nb, hkv, bs, d), -127, 128, jnp.int32).astype(dtype)
                for k_ in (kk, kv))
            scales = tuple(jax.random.uniform(
                k_, (nb, hkv, bs), jnp.float32, 0.004, 0.012)
                for k_ in jax.random.split(ks))
        else:
            q = q.astype(dtype)
            k_pool, v_pool = (jax.random.normal(
                k_, (pnb, phkv, bs, d), jnp.float32).astype(dtype)
                for k_ in (kk, kv))
        table = jnp.asarray(1 + np.arange(b * mb).reshape(b, mb), jnp.int32)
        if args.write:
            # each row's next ``lq`` positions, as the cached forwards
            # address them; an inactive slot writes the scratch block
            pools = (k_pool, v_pool)
            k_new, v_new = (jax.random.normal(
                k_, (b, hkv, lq, d), jnp.float32).astype(dtype)
                for k_ in jax.random.split(kq))
            for label, ctx in contexts_of(name, b, lq, mb, bs, mix).items():
                if label not in (args.context or ("mix", "chat")):
                    continue
                pos = np.asarray([[min(c, mb * bs - lq) + t
                                   for t in range(lq)] for c in ctx])
                live = np.asarray([c > lq for c in ctx])[:, None]
                phys = np.where(live, np.asarray(table)[
                    np.arange(b)[:, None], pos // bs], 0).astype(np.int32)
                off = np.where(live, pos % bs, 0).astype(np.int32)
                rest = (k_new, v_new, jnp.asarray(phys), jnp.asarray(off))
                rows_bytes = 2 * b * lq * hkv * d * dtype.itemsize
                line = {
                    "tree": os.path.relpath(os.path.abspath(args.tree), ROOT),
                    "device": "%s %s" % (device.platform, device.device_kind),
                    "geometry": "toy" if args.cpu_toy else name,
                    "what": "kv_write", "context": label, "calls": calls,
                    "rows": b * lq, "heads": hkv, "rows_bytes": rows_bytes}
                takes = "absent"
                if hasattr(fa, "paged_kv_write_route"):
                    with fa.decode_route("pallas"):
                        try:
                            takes = fa.paged_kv_write_route(k_pool, lq)
                        except Exception as e:  # noqa: BLE001 - by name
                            takes = "refused: " + str(e)[:200]
                line["kernel"] = takes if takes != "scatter" \
                    else "scatter by design"
                if takes == "kernel":
                    line["group_bytes"] = 2 * rows_bytes * pd.write_group(
                        bs, dtype.itemsize)
                wrote = {}
                for key, route in (("scatter", "composition"),
                                   ("kernel", "pallas")):
                    if key == "kernel" and takes != "kernel":
                        continue
                    fn = write_chain(route, **how)
                    # what one chain leaves in the pools, the scratch
                    # block left out (rows that repeat land in no order)
                    wrote[key] = [p[1:] for p in fn(
                        *(jnp.copy(p) for p in pools), *rest)]
                    pools, ms = write_ms(fn, pools, rest)
                    if device.platform == "tpu":
                        moved = line["group_bytes"] if key == "kernel" \
                            else rows_bytes
                        line[key + "_ms"] = ms
                        line[key + "_bytes_share"] = \
                            moved / HBM_BYTES_PER_S * 1e3 / ms
                if len(wrote) == 2:
                    line["same_bits"] = all(
                        bool(jnp.array_equal(a, b)) for a, b in
                        zip(wrote["scatter"], wrote["kernel"]))
                print(json.dumps(line), flush=True)
            continue
        plain = chain("composition", **how)
        for cap in args.entries or [None]:
            if cap is not None and hasattr(pd, "_PAGED_TILE_ENTRIES"):
                pd._PAGED_TILE_ENTRIES = cap
                pd._paged_call.clear_cache()
            kernel = chain("pallas", **how)
            for label, ctx in contexts_of(name, b, lq, span, bs,
                                          mix).items():
                if args.context and label not in args.context:
                    continue
                # a chunk's last query sees the row's last position
                q_pos = jnp.asarray(
                    [[c - lq + t for t in range(lq)] if c else [-1] * lq
                     for c in ctx], jnp.int32)
                operands = (q, k_pool, v_pool, table, q_pos) + scales
                want = plain(*operands)
                live = sum((c - 1) // bs + 1 for c in ctx if c) \
                    if window is None else sum(
                        (c - 1) // bs - max(c - window, 0) // bs + 1
                        for c in ctx if c)
                line = {
                    "tree": os.path.relpath(os.path.abspath(args.tree), ROOT),
                    "device": "%s %s" % (device.platform, device.device_kind),
                    "geometry": "toy" if args.cpu_toy else name,
                    "context": label, "calls": calls,
                    "tile_cap": getattr(pd, "_PAGED_TILE_ENTRIES", None),
                    "live_entries": live, "table_entries": b * mb,
                    **({} if window is None else {"window": window}),
                    "kv_budget": pd._KV_VMEM_BUDGET}
                try:
                    got = kernel(*operands)
                except Exception as e:  # noqa: BLE001 - a refusal, by name
                    got = None
                    line["refused"] = str(e)[:300]
                if got is not None:
                    # a row that sees nothing: the kernel's zeros against
                    # the composition's mean of every value; left out
                    seen = np.asarray([c > 0 for c in ctx])
                    diff = jnp.abs(got.astype(jnp.float32)
                                   - want.astype(jnp.float32))[seen]
                    line.update(
                        max_abs_diff=float(jnp.max(diff)),
                        max_abs=float(jnp.max(jnp.abs(
                            want.astype(jnp.float32))[seen])))
                if device.platform == "tpu":
                    # K and V of every live entry, once
                    least = live * 2 * hkv * bs * d * dtype.itemsize \
                        / HBM_BYTES_PER_S * 1e3
                    line["live_bytes_ms"] = least
                    if got is not None:
                        ms = ms_a_call(kernel, operands)
                        line.update(ms_a_call=ms,
                                    us_a_live_entry=ms * 1e3 / live,
                                    live_bytes_share=least / ms)
                    if args.composition:
                        line["composition_ms_a_call"] = ms_a_call(
                            plain, operands)
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
