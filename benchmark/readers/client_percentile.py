"""A percentile, over the requests of the whole window, of what the client
saw: ``what`` is ``ttft`` (due instant to first token line, over the
requests due in the window, failed ones as the worst) or ``tpot`` ((last -
first token) / (tokens - 1), over the requests that ended in it); ``q`` the
quantile.  In ms, on the client's clock."""
from harness import window


def read(ctx, params):
    if not ctx["records"]:
        return None
    a, b = ctx["client_window"]
    worst = 1e3 * (b - a + ctx["traffic"].get("drain_s", 10.0))
    fn = {"ttft": window.ttft_ms, "tpot": window.tpot_ms}[params["what"]]
    vals = fn(ctx["records"], a, b, worst)
    return window.percentile(vals, params["q"]) if vals else None
