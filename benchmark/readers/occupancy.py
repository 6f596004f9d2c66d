"""Live requests over slots, averaged over the window's time, in %.  Read
from the client's records, so with no tracer on: a request holds a slot
from its prefill, which emits its first token, to its terminal line."""


def read(ctx, params):
    if not ctx["records"]:
        return None
    a, b = ctx["client_window"]
    held = 0.0
    for r in ctx["records"]:
        if r["stamps"]:
            end = r["done"] if r["done"] is not None else b
            held += max(0.0, min(end, b) - max(r["stamps"][0], a))
    return 100.0 * held / ((b - a) * ctx["cfg"]["engine"]["slots"])
