"""The expert layers' grouped matmuls against the memory roofline, in %:
the least bytes they must read in a step (``harness/blockdiff_costs
.experts_min_bytes``: the touched experts' weights once a layer, the routed
rows in and out) over the chip's HBM bandwidth, over the device time of the
operations under the ``moe/experts`` scope inside a step.  That time is the
``scope_share`` reader's share times the runs' time, so both read the same
operations.  None where no operation runs under such a scope."""
from harness import blockdiff_costs, xplane
from readers import scope_share


def read(ctx, params):
    share = scope_share.read(ctx, params)
    if share is None:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    metas = [m for n, s, _, _, m in ctx["spans"] or []
             if n == "tick.decode" and ctx["t0"] <= s < ctx["t1"]
             and "rows" in m]
    if not metas:
        return None
    rows = sum(m["rows"] for m in metas) / len(metas)
    seconds = share / 100.0 * sum(e - s for s, e in runs) / len(runs)
    least = blockdiff_costs.experts_min_bytes(
        ctx["cfg"], rows, params["weight_bytes"]) \
        / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least / seconds
