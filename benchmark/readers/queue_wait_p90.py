"""``req.queued`` to ``req.prefilling`` per request, 90th percentile over
the requests queued in the traced stretch, in ms."""
from harness import window


def read(ctx, params):
    queued, waits = {}, []
    for n, s, _, rid, _ in ctx["spans"] or []:
        if n == "req.queued" and ctx["t0"] <= s < ctx["t1"]:
            queued[rid] = s
        elif n == "req.prefilling" and rid in queued:
            waits.append((s - queued.pop(rid)) * 1e3)
    return window.percentile(waits, 0.9) if waits else None
