"""Device time of the operations whose name matches ``op_pattern`` over
the device time of the executable runs that match ``pattern``, in %; 0
when the executable ran and no operation in it matches."""
import re

from harness import xplane


def read(ctx, params):
    if not ctx["trace"].devices:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    if not runs:
        return None
    rx = re.compile(params["op_pattern"])
    inside = xplane.ops_within(ctx["trace"], runs)
    matched = sum(v for k, v in inside.items() if rx.search(k))
    return 100.0 * matched / sum(e - s for s, e in runs)
