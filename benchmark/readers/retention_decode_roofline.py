"""The batched decode step of the power-retention decoder against the memory
roofline, in %: the least bytes a step must move (``harness/retention_costs
.decode_step_min_bytes``: the layers' weights and the head once, every live
row's state in and out) over the chip's HBM bandwidth, over the step's mean
device time.  Live rows come from the program's ``tick.decode`` spans.  None
where the program ran no such executable or writes no such spans."""
from harness import retention_costs, xplane
from readers.retention_step_roofline import live_rows


def read(ctx, params):
    if not ctx["trace"].devices:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    rows = live_rows(ctx)
    if not runs or rows is None:
        return None
    least = retention_costs.decode_step_min_bytes(
        ctx["cfg"], rows, params["weight_bytes"]) \
        / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least * len(runs) / sum(e - s for s, e in runs)
