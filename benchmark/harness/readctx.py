"""What a reader is given.  ``base`` holds what every run has: the client's
records and the window they are read over, the cell's data files, the
driver's own numbers.  ``traced`` adds the reduced device trace and the
program's spans on the trace's clock, once per traced run."""
from __future__ import annotations

from . import device, xplane

TICK_PHASES = ("tick", "tick.admit", "tick.prefill", "tick.decode",
               "tick.sample", "tick.deliver")


def base(run, got, summary, dev) -> dict:
    return {
        "records": got.get("records"),
        "client_window": (got["t_open"], got["t_close"]),
        "cfg": run["cfg"], "traffic": run["traffic"], "chips": run["chips"],
        "device": dev, "peaks": lambda: device.peaks(dev["kind"]),
        "summary": summary,
    }


def traced(ctx: dict, run, got) -> dict:
    trace = xplane.Trace.from_file(xplane.newest_xplane(run["trace_dir"]))
    offset = trace.sync_offset(got["syncs"])
    # serving: the profiler covers ``traced``, the stretch after the window
    # that the client's stamps are read over; training: the whole window
    host = got.get("traced") or (got["t_open"], got["t_close"])
    t0, t1 = host[0] + offset, host[1] + offset
    spans = [(n, s + offset, e + offset, rid, meta)
             for n, s, e, rid, meta in got["spans"] or []]
    phases = [(n, s, e) for n, s, e, _, _ in spans
              if n in TICK_PHASES and e > s]
    if got.get("marks"):        # training: the host's wait for each step
        edges = [got["t_open"]] + got["marks"]
        phases = [("host.wait_step", a + offset, b + offset)
                  for a, b in zip(edges, edges[1:])]
    ctx = dict(ctx, trace=trace, t0=t0, t1=t1, offset=offset, spans=spans,
               traced_window=host, spans_end=got.get("spans_end"),
               busy_s=xplane.busy_seconds(trace, t0, t1), window_s=t1 - t0)
    ctx["breakdown"] = {
        "device_ops": xplane.top(xplane.op_seconds(trace, t0, t1))
        if trace.devices else [],
        "idle_gaps": xplane.top(xplane.idle_gaps(trace, phases, t0, t1))
        if trace.devices else [],
    }
    return ctx
