"""Mean device time, in ms, of the runs inside the window of the
executable whose name matches ``pattern``."""
from harness import xplane


def read(ctx, params):
    if not ctx["trace"].devices:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / len(runs)
