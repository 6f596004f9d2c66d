"""The block step against the memory roofline, in %: the least bytes a step
must read (``harness/blockdiff_costs.step_min_bytes``: touched experts',
attention's and head's weights once, K/V of live positions) over the chip's
HBM bandwidth, over the step's mean device time.

Rows a step and forwards a token come from the program's ``tick.decode``
spans (``rows``, ``live``, ``committed``); live positions from the client's
records: a token line of a request that then holds p positions stands for
``forwards per token`` slot-forwards that read p positions each.  None
where the program writes no such spans or ran no such executable."""
from harness import blockdiff_costs, xplane


def read(ctx, params):
    if not ctx["trace"].devices or not ctx["records"]:
        return None
    t0, t1 = ctx["t0"], ctx["t1"]
    runs = xplane.module_runs(ctx["trace"], params["pattern"], t0, t1)
    metas = [m for n, s, _, _, m in ctx["spans"] or []
             if n == "tick.decode" and t0 <= s < t1 and "rows" in m]
    committed = sum(m["committed"] for m in metas)
    if not runs or not metas or not committed:
        return None
    rows = sum(m["rows"] for m in metas) / len(metas)
    forwards_per_token = sum(m["live"] for m in metas) / committed
    off = ctx["offset"]
    positions = forwards_per_token * sum(
        r["prompt_tokens"] + k for r in ctx["records"]
        for k, s in enumerate(r["stamps"]) if t0 <= s + off < t1)
    least = blockdiff_costs.step_min_bytes(
        ctx["cfg"], rows, positions / len(runs), params["weight_bytes"],
        params["kv_bytes"]) / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least * len(runs) / sum(e - s for s, e in runs)
