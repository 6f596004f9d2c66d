"""What the program's instants say, over those named ``instant`` that were
emitted in the traced stretch, times ``scale`` (1 where not given): with
``num`` and ``den``, the two meta keys each summed and divided (a mean a
line from what each request's ``http.stream`` summed); with ``key`` and
``q``, the ``q`` quantile of that key over the instants (a request's worst
line, over requests).  None where no such instant carries the keys, or the
denominators sum to nothing: a program that does not emit them."""
from harness import window


def read(ctx, params):
    metas = [m for n, s, _, _, m in ctx["spans"] or []
             if n == params["instant"] and ctx["t0"] <= s < ctx["t1"]]
    scale = params.get("scale", 1.0)
    if "key" in params:
        vals = [m[params["key"]] for m in metas if params["key"] in m]
        return scale * window.percentile(vals, params["q"]) if vals \
            else None
    num, den = params["num"], params["den"]
    both = [m for m in metas if num in m and den in m]
    total = sum(m[den] for m in both)
    if not total:
        return None
    return scale * sum(m[num] for m in both) / total
