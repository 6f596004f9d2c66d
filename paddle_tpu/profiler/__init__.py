"""``paddle_tpu.profiler`` — host-side op profiler + XLA trace capture.

Reference parity: ``python/paddle/fluid/profiler.py`` —
``start_profiler:222`` / ``stop_profiler:262`` / ``profiler:314`` (context),
with the sorted-summary table the reference prints from its C++ event
tracer.  TPU-native additions: ``xla_trace`` wraps ``jax.profiler``
(TensorBoard-consumable device traces — the nvprof analog), and ``StepTimer``
computes step time + MFU (BASELINE.md's metric) against ``DEVICE_PEAKS``.

Consumes ``FLAGS_benchmark``: while profiling (or when the flag is set) each
dispatched op is timed host-side with a block-until-ready, trading pipelining
for accurate per-op wall time — exactly the reference flag's semantics.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Optional

import jax

from ..core import flags as _flags
from ..core.errors import InvalidArgumentError, NotFoundError

__all__ = ["start_profiler", "stop_profiler", "profiler", "xla_trace",
           "StepTimer", "is_profiling", "record_op_time"]

_active = False
_events = defaultdict(lambda: [0, 0.0])  # name → [count, total_s]


def is_profiling() -> bool:
    return _active or _flags.flag("FLAGS_benchmark")


def record_op_time(name: str, seconds: float) -> None:
    _events[name][0] += 1
    _events[name][1] += seconds


def start_profiler(state: str = "All", tracer_option: str = "Default") -> None:
    """profiler.py:222 parity."""
    global _active
    if state not in ("CPU", "GPU", "All"):
        raise InvalidArgumentError(
            "profiler state must be CPU/GPU/All, got %r" % state)
    _events.clear()
    _active = True


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: Optional[str] = None) -> str:
    """profiler.py:262 parity: stop and print/return the summary table."""
    global _active
    _active = False
    keys = {"calls": lambda kv: kv[1][0], "total": lambda kv: kv[1][1],
            "max": lambda kv: kv[1][1], "min": lambda kv: kv[1][1],
            "ave": lambda kv: kv[1][1] / max(kv[1][0], 1), None: lambda kv: 0}
    if sorted_key not in keys:
        raise InvalidArgumentError(
            "sorted_key must be calls/total/ave/max/min/None, got %r"
            % sorted_key)
    rows = sorted(_events.items(), key=keys[sorted_key], reverse=True)
    lines = ["%-40s %10s %15s %15s" % ("Event", "Calls", "Total(ms)", "Ave(ms)")]
    for name, (calls, total) in rows:
        lines.append("%-40s %10d %15.3f %15.3f"
                     % (name, calls, total * 1e3, total / max(calls, 1) * 1e3))
    table = "\n".join(lines)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(table)
    else:
        print(table)
    return table


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None, tracer_option: str = "Default"):
    """profiler.py:314 parity context."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def xla_trace(log_dir: str):
    """Device-side trace via jax.profiler (view in TensorBoard/xprof)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Step wall-time + throughput + MFU (BASELINE.md metric) helper."""

    def __init__(self, flops_per_step: float = 0.0,
                 peak_flops: Optional[float] = None,
                 items_per_step: float = 0.0):
        self.flops_per_step = flops_per_step
        self.items_per_step = items_per_step
        # resolved on first use (``mfu``): the table raises for a device
        # it does not list, and timing steps on such a device is fine
        self.peak_flops = peak_flops
        self._t0 = None
        self.steps = 0
        self.total = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.total += time.perf_counter() - self._t0
        self.steps += 1

    @property
    def step_time(self) -> float:
        return self.total / max(self.steps, 1)

    @property
    def items_per_sec(self) -> float:
        return self.items_per_step / self.step_time if self.total else 0.0

    @property
    def mfu(self) -> float:
        if not (self.flops_per_step and self.total):
            return 0.0
        if self.peak_flops is None:
            self.peak_flops = device_peak_flops()
        return self.flops_per_step / self.step_time / self.peak_flops


# THE peaks table: published per-chip peaks keyed by jax's
# ``device_kind``.  ``StepTimer.mfu``, ``chip_smoke.py`` and the expert
# route's cost rule (``nn/functional/moe.py``) all read it, and a device that is not listed is an error, never a default — a
# utilisation against an assumed peak is not a measurement.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s in bf16,
# 16 GB of HBM at 819 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_sec": 819e9},
}


def device_peaks(device_kind: Optional[str] = None) -> dict:
    """``DEVICE_PEAKS`` row for ``device_kind`` (default: the first
    device jax reports); raises for a device the table does not list."""
    kind = jax.devices()[0].device_kind if device_kind is None \
        else device_kind
    peaks = DEVICE_PEAKS.get(kind)
    if peaks is None:
        raise NotFoundError(
            "no published peaks for device_kind %r (known: %s); add the "
            "device to profiler.DEVICE_PEAKS with its source, or pass "
            "an explicit peak" % (kind, sorted(DEVICE_PEAKS)))
    return peaks


def device_peak_flops(device_kind: Optional[str] = None) -> float:
    """Per-chip bf16 peak FLOP/s (MFU convention) from the peaks table."""
    return device_peaks(device_kind)["bf16_flops"]


from .visual import LogWriter, export_chrome_tracing  # noqa: E402,F401

__all__ += ["LogWriter", "export_chrome_tracing"]
