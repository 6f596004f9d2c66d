"""A percentile of a field of the terminal ndjson line, which the engine
writes with no tracer on (``lock_wait_s``, ``queue_wait_s``): ``field`` in
seconds on the line, the result in ms; ``q`` the quantile.  Read over the
client's window, so in a traced run over the stretch before anything
traces.  ``over`` says which requests: ``due`` (the default), those due in
the window, or ``ended``, those whose terminal line arrived in it (a closed
loop's requests wait out the window in the queue: the ones sent in it end
long after the run).

A request that failed, was refused or never got a token counts as the
worst; one still streaming when the run ended is left out, since its waits
ended before its first token and only the line that carries them was never
written.  None where no terminal line has the field: a program that does
not write it."""
from harness import window


def read(ctx, params):
    if not ctx["records"]:
        return None
    a, b = ctx["client_window"]
    field = params["field"]
    if params.get("over", "due") == "ended":
        mine = [r for r in ctx["records"]
                if r["done"] is not None and a <= r["done"] < b]
    else:
        mine = [r for r in ctx["records"] if a <= r["due"] < b]
    if not any(field in (r.get("final") or {}) for r in mine):
        return None
    worst = 1e3 * (b - a + ctx["traffic"].get("drain_s", 10.0))
    vals = []
    for r in mine:
        value = (r.get("final") or {}).get(field)
        if value is not None:
            vals.append(1e3 * value)
        elif r["status"] in ("failed", "refused") or not r["stamps"]:
            vals.append(worst)
    return window.percentile(vals, params["q"]) if vals else None
