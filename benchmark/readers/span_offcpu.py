"""Mean, over the occurrences of ``span`` inside the traced stretch, of the
time its thread did not run: the span's length less the ``cpu_s`` its meta
carries (the thread's CPU time over the span, ``serving/trace.py``), in ms.
With ``less``, the same of every span of those names that lies inside the
occurrence is taken off: what a tick waited, less what it waited for the
device in ``tick.sample`` and ``tick.prefill``, is what it waited for the
host.  A span without ``cpu_s`` is left out, as a whole occurrence or as one
of ``less``; None where no occurrence carries it: a program that does not
write it."""
import bisect


def off_cpu(s, e, meta):
    return (e - s) - meta["cpu_s"]


def read(ctx, params):
    spans = ctx["spans"] or []
    name, less = params["span"], set(params.get("less", ()))
    mine = [(s, e, m) for n, s, e, _, m in spans
            if n == name and s >= ctx["t0"] and e <= ctx["t1"]
            and "cpu_s" in m]
    if not mine:
        return None
    inner = sorted(((s, e, m) for n, s, e, _, m in spans
                    if n in less and "cpu_s" in m), key=lambda x: x[:2])
    starts = [s for s, _, _ in inner]
    total = 0.0
    for s, e, m in mine:
        inside = inner[bisect.bisect_left(starts, s):
                       bisect.bisect_left(starts, e)]
        total += off_cpu(s, e, m) - sum(off_cpu(*c) for c in inside
                                        if c[1] <= e)
    return 1e3 * total / len(mine)
