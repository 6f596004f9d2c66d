"""The batched decode step against the memory roofline, in %: the least
bytes a step must read (every weight once, and the K and V of every live
position, from shapes) over the chip's HBM bandwidth, over the step's mean
device time.  Memory-bound: at 16 rows the step's FLOPs over the compute
peak are a fortieth of that time.  Live positions are counted from the
client's records: a token line of a request that then holds p positions
stands for one row of one step reading p positions."""
from harness import device, xplane


def read(ctx, params):
    if not ctx["trace"].devices or not ctx["records"]:
        return None
    t0, t1 = ctx["t0"], ctx["t1"]
    runs = xplane.module_runs(ctx["trace"], params["pattern"], t0, t1)
    if not runs:
        return None
    off = ctx["offset"]
    positions = sum(r["prompt_tokens"] + k for r in ctx["records"]
                    for k, s in enumerate(r["stamps"])
                    if k > 0 and t0 <= s + off < t1)
    cfg = ctx["cfg"]
    least = device.decode_step_min_bytes(
        cfg, positions / len(runs), params["weight_bytes"],
        params["kv_bytes"]) / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least * len(runs) / sum(e - s for s, e in runs)
