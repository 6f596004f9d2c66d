"""The batched decode step of the hybrid Mamba / attention decoder against
the memory roofline, in %: the least bytes a step must move
(``harness/mamba_costs.decode_step_min_bytes``: the weights once, every live
row's scan and convolution state in and out, the K/V blocks the live rows'
positions reach) over the chip's HBM bandwidth, over the step's mean device
time.  Live rows and live blocks come from the program's ``tick.decode``
spans (``live``, ``live_blocks``).  None where the program ran no such
executable or writes no such spans."""
from harness import mamba_costs, xplane


def mean_meta(ctx, key):
    vals = [m[key] for n, s, _, _, m in ctx["spans"] or []
            if n == "tick.decode" and ctx["t0"] <= s < ctx["t1"]
            and key in m]
    return sum(vals) / len(vals) if vals else None


def read(ctx, params):
    if not ctx["trace"].devices:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    rows, blocks = mean_meta(ctx, "live"), mean_meta(ctx, "live_blocks")
    if not runs or rows is None or blocks is None:
        return None
    least = mamba_costs.decode_step_min_bytes(
        ctx["cfg"], rows, blocks, params["weight_bytes"]) \
        / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least * len(runs) / sum(e - s for s, e in runs)
