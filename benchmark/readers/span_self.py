"""Mean, over the occurrences of ``span`` inside the traced stretch, of its
length less the union of the ``children`` spans that lie inside it: the
time the span spent in none of them, in ms.  None where the span does not
occur."""
import bisect

from harness import xplane


def read(ctx, params):
    spans = ctx["spans"] or []
    name, children = params["span"], set(params["children"])
    mine = [(s, e) for n, s, e, _, _ in spans
            if n == name and s >= ctx["t0"] and e <= ctx["t1"]]
    if not mine:
        return None
    inner = sorted((s, e) for n, s, e, _, _ in spans if n in children)
    starts = [s for s, _ in inner]
    own = 0.0
    for s, e in mine:
        inside = inner[bisect.bisect_left(starts, s):
                       bisect.bisect_left(starts, e)]
        own += (e - s) - sum(min(ce, e) - cs
                             for cs, ce in xplane.union(inside))
    return 1e3 * own / len(mine)
