"""The selective scans of the prefills against the memory roofline, in %: the
least bytes they move (``harness/mamba_costs.prefill_scan_min_bytes``: ``dt``
and ``c`` read once and ``y`` written once a position a Mamba layer, the
state once in and out) over the chip's HBM bandwidth, over the device time of
the operations under the ``mamba/scan_chunk`` scope inside the prefills'
runs.  The positions are the buckets of the program's ``tick.prefill`` spans
(``bucket``), their mean; the time is the ``scope_share`` reader's share
times the runs' mean time.  The scan is bound by its exponentials (one a
state entry a position, sixteen a float it reads), not by these bytes: this
share reads low by construction, and says how far.  None where no operation
runs under such a scope or the program writes no such spans."""
from harness import mamba_costs, xplane
from readers import scope_share


def read(ctx, params):
    share = scope_share.read(ctx, params)
    buckets = [m["bucket"] for n, s, _, _, m in ctx["spans"] or []
               if n == "tick.prefill" and ctx["t0"] <= s < ctx["t1"]
               and "bucket" in m]
    if share is None or not buckets:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    seconds = share / 100.0 * sum(e - s for s, e in runs) / len(runs)
    least = mamba_costs.prefill_scan_min_bytes(
        ctx["cfg"], sum(buckets) / len(buckets)) \
        / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least / seconds
