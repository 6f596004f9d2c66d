"""The selective-scan steps of a decode step against the memory roofline, in
%: the least bytes they move (``harness/mamba_costs.scan_step_min_bytes``:
every live row's scan state and convolution state read once and written once
in every Mamba layer) over the chip's HBM bandwidth, over the device time of
the operations under the ``mamba/scan_step`` scope inside a step.  That time
is the ``scope_share`` reader's share times the runs' time, so both read the
same operations.  Live rows come from the program's ``tick.decode`` spans
(``live``).  Memory-bound: the step multiplies and adds a few times and takes
one exponential for each float it moves.  None where no operation runs under
such a scope or the program writes no such spans."""
from harness import mamba_costs, xplane
from readers import scope_share
from readers.retention_step_roofline import live_rows


def read(ctx, params):
    share = scope_share.read(ctx, params)
    rows = live_rows(ctx)
    if share is None or rows is None:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    seconds = share / 100.0 * sum(e - s for s, e in runs) / len(runs)
    least = mamba_costs.scan_step_min_bytes(ctx["cfg"], rows) \
        / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least / seconds
