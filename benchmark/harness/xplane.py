"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and
per-operation times.  Reads with ``jax.profiler.ProfileData`` alone.

A device plane is one whose name matches ``DEVICE_PLANE``; its ``XLA Ops``
line holds one event per operation run, its ``XLA Modules`` line one per
executable run.  Times are seconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SYNC_NAME = "bench.sync"
SHORT_GAP_S = 50e-6


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO line (``%fusion.3 =
    f32[..] fusion(..)``); the instruction's name is what is kept."""
    return name.split(" = ", 1)[0].lstrip("%")


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(found, key=os.path.getmtime)


class Trace:
    """``devices``: per device plane ``{"ops": [...], "modules": [...]}`` of
    ``(name, start_s, end_s)``; ``host``: ``{line name: [...]}`` likewise."""

    def __init__(self, devices: dict, host: dict):
        self.devices, self.host = devices, host

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        devices, host = {}, {}
        for plane in ProfileData.from_file(path).planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                evs = [(short_name(e.name), e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                if m:
                    key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                        line.name)
                    if key:
                        devices.setdefault(int(m.group(1)), {
                            "ops": [], "modules": []})[key] += evs
                elif plane.name.startswith("/host:"):
                    host.setdefault(line.name, []).extend(evs)
        return cls(devices, host)

    def sync_offset(self, stamps_s: list) -> float:
        """Trace clock minus the host's ``perf_counter``, from the
        ``bench.sync`` annotations emitted at the ``stamps_s`` instants."""
        found = sorted(s for evs in self.host.values()
                       for n, s, _ in evs if n == SYNC_NAME)
        if not found or len(found) != len(stamps_s):
            raise ValueError("%d %s annotations in the trace, %d emitted"
                             % (len(found), SYNC_NAME, len(stamps_s)))
        diffs = sorted(f - s for f, s in zip(found, sorted(stamps_s)))
        return diffs[len(diffs) // 2]


def clip(events, t0: float, t1: float) -> list:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace, t0: float, t1: float) -> float:
    """Union of the device-operation intervals inside the window, averaged
    over the devices."""
    per = [sum(e - s for s, e in union(
        (s, e) for _, s, e in clip(d["ops"], t0, t1)))
        for d in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def op_seconds(trace: Trace, t0: float, t1: float, device: int = 0) -> dict:
    """Seconds per operation name on one device, inside the window."""
    out = {}
    for n, s, e in clip(trace.devices[device]["ops"], t0, t1):
        out[n] = out.get(n, 0.0) + (e - s)
    return out


def module_runs(trace: Trace, pattern: str, t0: float, t1: float,
                device: int = 0) -> list:
    """``(start, end)`` of every run, wholly inside the window, of an
    executable whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [(s, e) for n, s, e in trace.devices[device]["modules"]
            if rx.search(n) and s >= t0 and e <= t1]


def ops_within(trace: Trace, runs: list, device: int = 0) -> dict:
    """Seconds per operation name inside the given executable runs."""
    out, ops = {}, sorted(trace.devices[device]["ops"], key=lambda x: x[1])
    i = 0
    for rs, re_ in sorted(runs):
        while i < len(ops) and ops[i][2] <= rs:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < re_:
            n, s, e = ops[j]
            out[n] = out.get(n, 0.0) + (min(e, re_) - max(s, rs))
            j += 1
    return out


def idle_gaps(trace: Trace, spans: list, t0: float, t1: float,
              device: int = 0) -> dict:
    """Idle seconds of one device inside the window, by what the host was
    doing: each gap between operations goes to the innermost of ``spans``
    (``(name, start, end)`` on the trace's clock) that holds its middle,
    ``outside_spans`` if none does; gaps under 50 us are pooled."""
    busy = union((s, e) for _, s, e in clip(trace.devices[device]["ops"],
                                            t0, t1))
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    out = {}
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        if ge - gs < SHORT_GAP_S:
            name = "pauses_under_50us"
        else:
            mid = (gs + ge) / 2
            holding = [(e - s, n) for n, s, e in spans if s <= mid < e]
            name = min(holding)[1] if holding else "outside_spans"
        out[name] = out.get(name, 0.0) + (ge - gs)
    return out


def top(mapping: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(mapping.items(),
                                      key=lambda kv: -kv[1])[:n]]
