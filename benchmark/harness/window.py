"""Window arithmetic on the client's stamps.  Pure functions: a window is
two instants on the client's clock, never a request boundary.

A record is a dict the client fills: ``due`` (the instant it was due, or
sent in a closed loop), ``sent``, ``stamps`` (the arrival instant of every
token line), ``done`` (arrival of the terminal line, or None) and
``status``: ``ok``, ``inflight``, ``failed`` or ``refused``.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics; ``inf`` entries are legal and sort last."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no samples")
    pos = q * (len(vals) - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or vals[lo] == vals[hi]:
        return vals[lo]
    if math.isinf(vals[hi]):
        return vals[hi]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def tokens_in_window(records, t0: float, t1: float) -> int:
    """Every token line that arrived in ``[t0, t1)``, whether or not its
    request ended, or even began, inside the window."""
    return sum(1 for r in records for s in r["stamps"] if t0 <= s < t1)


def ttft_ms(records, t0: float, t1: float, worst_ms: float) -> list:
    """Due instant to first token line, for every request due in the
    window.  A request that failed, was refused or never got a token
    counts as ``worst_ms``."""
    out = []
    for r in records:
        if t0 <= r["due"] < t1:
            if r["status"] in ("failed", "refused") or not r["stamps"]:
                out.append(worst_ms)
            else:
                out.append((r["stamps"][0] - r["due"]) * 1e3)
    return out


def tpot_ms(records, t0: float, t1: float, worst_ms: float) -> list:
    """(last token - first token) / (tokens - 1) for every request that
    ended in the window; a request that failed in it counts as worst."""
    out = []
    for r in records:
        if r["status"] in ("failed", "refused"):
            if r["done"] is not None and t0 <= r["done"] < t1:
                out.append(worst_ms)
        elif r["done"] is not None and t0 <= r["done"] < t1 \
                and len(r["stamps"]) > 1:
            out.append((r["stamps"][-1] - r["stamps"][0]) * 1e3
                       / (len(r["stamps"]) - 1))
    return out


def lateness_ms(records, t0: float, t1: float) -> list:
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if t0 <= r["due"] < t1 and r["sent"] is not None]


def counts(records, t0: float, t1: float) -> dict:
    """Attempted and failed among the requests due in the window."""
    due = [r for r in records if t0 <= r["due"] < t1]
    return {"attempted": len(due),
            "failed": sum(r["status"] in ("failed", "refused") for r in due)}
