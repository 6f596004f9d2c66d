"""On-chip ResNet50 train-step diagnosis (VERDICT r3 next #1 evidence).

Times the full TrainStep (device-resident inputs, bench.py's own timing
helper) across layout x batch x precision, and optionally captures a JAX
profiler trace of the winning configuration.  Writes a JSON report to
tools/resnet_perf_report.json and prints one line per leg.

Run on the machine with the chip (it refuses to time the CPU):
    python tools/resnet_perf.py [--trace]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

from bench import (RESNET_MFU_CONVENTION, _peak_flops, _time_steps,
                   resnet50_mfu, wrap_resnet_remat)
from tools.compile_cache import ensure_compile_cache


def build_step(pt, fmt, amp, classes=1000, remat=False, s2d=False):
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    pt.seed(0)
    model = resnet50(num_classes=classes, data_format=fmt,
                     space_to_depth_stem=s2d)
    if remat:
        # re-run each residual block in backward instead of keeping its
        # activations (shared mitigation with the bench's remat leg)
        wrap_resnet_remat(model)
    criterion = pt.nn.CrossEntropyLoss()
    opt = pt.optimizer.Momentum(0.1, parameters=model.parameters())
    if amp:
        model, opt = pt.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16")

        def loss_fn(m, x, y):
            with pt.amp.auto_cast(level="O1", dtype="bfloat16"):
                return criterion(m(x), y)
    else:
        def loss_fn(m, x, y):
            return criterion(m(x), y)
    return TrainStep(model, loss_fn, opt)


def leg_dict(fmt, amp, batch, s2d, remat, dt, peak):
    """The one leg-record shape.

    MFU comes from bench.resnet50_mfu — the same formula and
    mfu_convention stamp as bench_resnet50's records, so consumers
    see one convention."""
    return {"fmt": fmt, "amp": amp, "batch": batch, "s2d": s2d,
            "remat": remat, "step_s": round(dt, 5),
            "imgs_per_sec": round(batch / dt, 1),
            "mfu": round(resnet50_mfu(batch, dt, peak), 4),
            "mfu_convention": RESNET_MFU_CONVENTION}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace of the best leg")
    ap.add_argument("--batches", type=int, nargs="+",
                    default=[64, 128, 256])
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("resnet_perf times the chip; jax found %r"
                 % jax.devices()[0].platform)
    ensure_compile_cache()

    import paddle_tpu as pt

    on_tpu = True
    peak = _peak_flops(jax, on_tpu)
    rng = np.random.RandomState(0)
    report = []
    best = None  # (leg_dict, (fmt, amp, batch, remat, s2d)) — config only
    for fmt, s2d in (("NHWC", True), ("NHWC", False), ("NCHW", False)):
        for amp in (True, False):
            step = None
            for batch in args.batches:
                imgs = rng.randn(batch, 3, 224, 224).astype("float32")
                labels = rng.randint(0, 1000, (batch,)).astype("int64")
                try:
                    if step is None:
                        step = build_step(pt, fmt, amp, s2d=s2d)
                    dt, _ = _time_steps(step, (imgs, labels),
                                        12 if on_tpu else 2)
                except Exception as e:  # noqa: BLE001 - OOM legs
                    report.append({"fmt": fmt, "amp": amp, "batch": batch,
                                   "s2d": s2d, "error": str(e)[:160]})
                    print("%s s2d=%s amp=%s b%d: FAILED %s"
                          % (fmt, s2d, amp, batch, str(e)[:80]), flush=True)
                    continue
                leg = leg_dict(fmt, amp, batch, s2d, False, dt, peak)
                report.append(leg)
                print("%s s2d=%s amp=%s b%d: %.4fs  %.0f img/s  MFU %.3f"
                      % (fmt, s2d, amp, batch, dt, batch / dt, leg["mfu"]),
                      flush=True)
                if best is None or leg["mfu"] > best[0]["mfu"]:
                    best = (leg, (fmt, amp, batch, False, s2d))
            del step  # one live model at a time (HBM)

    # remat pass: the large batches that spill without it, using the best
    # layout/precision found above
    if best is not None and on_tpu:
        fmt, amp, s2d = best[1][0], best[1][1], best[1][4]
        step = None
        # the spill-prone sizes: anything at/above the largest requested
        # batch, extended one doubling beyond it
        remat_batches = sorted({max(args.batches), max(args.batches) * 2})
        for batch in remat_batches:
            imgs = rng.randn(batch, 3, 224, 224).astype("float32")
            labels = rng.randint(0, 1000, (batch,)).astype("int64")
            try:
                if step is None:
                    step = build_step(pt, fmt, amp, remat=True, s2d=s2d)
                dt, _ = _time_steps(step, (imgs, labels), 12)
            except Exception as e:  # noqa: BLE001
                report.append({"fmt": fmt, "amp": amp, "batch": batch,
                               "remat": True, "s2d": s2d,
                               "error": str(e)[:160]})
                print("remat %s amp=%s b%d: FAILED %s"
                      % (fmt, amp, batch, str(e)[:80]), flush=True)
                continue
            leg = leg_dict(fmt, amp, batch, s2d, True, dt, peak)
            report.append(leg)
            print("remat %s amp=%s b%d: %.4fs  %.0f img/s  MFU %.3f"
                  % (fmt, amp, batch, dt, batch / dt, leg["mfu"]),
                  flush=True)
            if leg["mfu"] > best[0]["mfu"]:
                best = (leg, (fmt, amp, batch, True, s2d))
        del step

    if args.trace and best is not None:
        leg, (fmt, amp, batch, remat, s2d) = best
        step = build_step(pt, fmt, amp, remat=remat, s2d=s2d)
        imgs = jax.device_put(
            rng.randn(batch, 3, 224, 224).astype("float32"))
        labels = jax.device_put(
            rng.randint(0, 1000, (batch,)).astype("int64"))
        tracedir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "resnet_trace")
        step(imgs, labels)  # compile outside the trace window
        with jax.profiler.trace(tracedir):
            for _ in range(3):
                loss = step(imgs, labels)
            float(loss.value)
        print("trace written to", tracedir)

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "resnet_perf_report.json")
    with open(out, "w") as f:
        json.dump({"backend": jax.default_backend(), "legs": report,
                   "best": best[0] if best else None}, f, indent=2)
    print("report:", out)


if __name__ == "__main__":
    main()
