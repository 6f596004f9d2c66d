"""Device time of the operations traced under a scope that matches
``scope_pattern`` (``jax.named_scope``: the module tree of
``nn.Layer.__call__`` and the hand-placed ``lm_head``, ``sample``, ``loss``,
``optimizer``), inside the runs of the executable matching ``pattern``, over
those runs' device time, in %.  A fusion counts under the scope of its root.

The scope is read from the cell's own ``.xplane.pb`` under ``trace_dir``
(relative to ``benchmark/``), which the event metadata's ``tf_op`` stat
carries on a TPU v5 lite (``harness/opmeta.py``).  None, with a line
printed and never 0, where no operation of the executable is under such a
scope: a program without the scopes, or an executable that the persistent
compile cache kept from one (its key leaves metadata out unless
``jax_compilation_cache_include_metadata_in_key`` is set, as the program
now sets it)."""
import os
import re

from harness import opmeta, xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx, params):
    trace = ctx["trace"]
    if not trace.devices:
        return None
    runs = xplane.module_runs(trace, params["pattern"], ctx["t0"],
                              ctx["t1"])
    if not runs:
        return None
    rx = re.compile(params["pattern"])
    programs = {int(m.group(1)) for n, _, _ in trace.devices[0]["modules"]
                if rx.search(n)
                for m in [re.search(r"\((\d+)\)$", n)] if m}
    scopes = opmeta.op_scopes(xplane.newest_xplane(
        os.path.join(BENCH, params["trace_dir"])))
    wanted = re.compile(params["scope_pattern"])
    matched = sum(
        seconds for op, seconds in xplane.ops_within(trace, runs).items()
        if any(wanted.search(scopes.get((p, op), "")) for p in programs))
    if not matched:
        print("[scope_share] no operation of %s runs under a scope matching "
              "%r: not reported" % (params["pattern"],
                                    params["scope_pattern"]), flush=True)
        return None
    return 100.0 * matched / sum(e - s for s, e in runs)
