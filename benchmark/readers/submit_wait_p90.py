"""From the client's send instant to the engine's ``req.queued`` instant
(both on this process's clock): the HTTP front's accept, parse and
``ServingEngine.submit``'s wait for the engine lock; 90th percentile over
the requests sent in the traced stretch, in ms.  A request not yet queued
when the engine's tracer was switched off, after the drain, counts with
the wait it had had by then.  Read with the engine's tracer on, which
shortens this wait (PERF.md, Findings)."""
from harness import window


def read(ctx, params):
    if not ctx["records"] or not ctx["spans"]:
        return None
    off, (a, b) = ctx["offset"], ctx["traced_window"]
    queued = {rid: s - off for n, s, _, rid, _ in ctx["spans"]
              if n == "req.queued"}
    waits = [(queued.get("b%d" % r["index"], ctx["spans_end"]) - r["sent"])
             * 1e3 for r in ctx["records"]
             if r.get("sent") is not None and a <= r["sent"] < b]
    return window.percentile(waits, 0.9) if waits else None
