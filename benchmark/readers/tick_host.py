"""Mean over the engine's ticks of the tick span less the time a device
operation ran inside it: the host's own share of a tick, in ms."""
import bisect

from harness import xplane


def read(ctx, params):
    ticks = [(s, e) for n, s, e, _, _ in ctx["spans"]
             if n == params.get("span", "tick")
             and s >= ctx["t0"] and e <= ctx["t1"]]
    if not ticks or not ctx["trace"].devices:
        return None
    busy = xplane.union((s, e) for _, s, e in ctx["trace"].devices[0]["ops"])
    starts = [s for s, _ in busy]
    host = 0.0
    for ts, te in ticks:
        i = max(0, bisect.bisect_right(starts, ts) - 1)
        covered = 0.0
        while i < len(busy) and busy[i][0] < te:
            covered += max(0.0, min(busy[i][1], te) - max(busy[i][0], ts))
            i += 1
        host += (te - ts) - covered
    return 1e3 * host / len(ticks)
