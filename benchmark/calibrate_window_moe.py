"""A run of a window / global expert cell that also reads the controls:
what a program computing below the stated precision, letting its window
layers attend their whole context, reading the router from the
post-attention norm, or gating the experts by ``silu`` would have served at
the same positions (``harness/window_moe.py`` with
``harness/window_moe_reference.py``), their numbers beside the run's own in
``compared`` as ``control_<mode>_*`` (held to nothing).  The limits in the
configuration's file are set between the readings.

    python3 benchmark/calibrate_window_moe.py --workload <cell> --seed <n>
        --seconds <s> --trace 0

And the rooflines that PERF.md section 7 defines for the cell and the
manifest has no room for, by hand from the result line of a TRACED run (a
file holding that line; ``--meta`` the means of ``tick.decode``'s
``live``, ``live_blocks`` and ``window_live_blocks`` over the traced
stretch, ``--windowed-ms`` / ``--experts-ms`` / ``--band-ms`` the device
time a step spends under ``paged_attn/window`` and ``moe/experts`` and a
prefill under ``prefill_attn/band``, read from the trace by scope) and
``harness/window_moe_costs.py``:

    python3 benchmark/calibrate_window_moe.py --by-hand <file>
        --workload <cell> [--meta live,live_blocks,window_live_blocks]
        [--windowed-ms x] [--experts-ms x] [--band-ms x --band-tokens n]
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run                                         # noqa: E402
from harness import device, window_moe_costs as costs           # noqa: E402
from harness import window_moe_reference                        # noqa: E402


def _arg(name, default=None):
    return sys.argv[sys.argv.index(name) + 1] if name in sys.argv \
        else default


def by_hand(line: dict, cfg: dict) -> dict:
    """``decode_step_roofline``, ``windowed_calls_roofline``,
    ``moe_experts_roofline`` and ``prefill_band_roofline`` in %, from a
    traced result line and what the trace's scopes gave: the least bytes
    (``window_moe_costs``) over the chip's HBM bandwidth over the device
    time; the band's operations over the chip's bfloat16 peak."""
    m = {k: v["value"] for k, v in line["metrics"].items()}
    e = cfg["engine"]
    peaks = device.peaks(line["device"]["kind"])
    per_s = peaks["hbm_bytes_per_s"]
    table = e["slots"] * -(-cfg["max_len"] // e["block_size"])
    live_blocks = m["live_block_share.jamba"] / 100.0 * table
    rows = m.get("live_slots_per_step.jamba", e["slots"])
    meta = _arg("--meta")
    if meta:
        rows, live_blocks, window_live = (float(x) for x in meta.split(","))
    else:
        # every live row deep enough to fill its ring
        window_live = rows * costs.ring_blocks(cfg)
    step_s = m["decode_step_ms.jamba"] / 1e3
    out = {"rows": rows, "live_blocks": live_blocks,
           "window_live_blocks": window_live,
           "decode_step_roofline": 100.0 * costs.decode_step_min_bytes(
               cfg, rows, live_blocks, window_live, 2) / per_s / step_s}
    if _arg("--windowed-ms"):
        out["windowed_calls_roofline"] = 100.0 \
            * costs.windowed_calls_min_bytes(cfg, window_live) / per_s \
            / (float(_arg("--windowed-ms")) / 1e3)
    if _arg("--experts-ms"):
        out["moe_experts_roofline"] = 100.0 \
            * costs.moe_experts_min_bytes(cfg, rows, 2) / per_s \
            / (float(_arg("--experts-ms")) / 1e3)
    if _arg("--band-ms"):
        out["prefill_band_roofline"] = 100.0 \
            * costs.layer_kinds(cfg)[1] * costs.prefill_attention_flops(
                cfg, int(_arg("--band-tokens")), True) \
            / peaks["bf16_flops_per_s"] / (float(_arg("--band-ms")) / 1e3)
    return out


if __name__ == "__main__":
    if "--by-hand" in sys.argv:
        with open(_arg("--by-hand")) as f:
            line = json.loads(f.read().strip().splitlines()[-1])
        print("[by_hand] " + json.dumps(by_hand(
            line, bench_run.load_cell(_arg("--workload"))["cfg"])))
        sys.exit(0)
    sys.exit(bench_run.main(
        hooks={"measure": {"controls": window_moe_reference.CONTROLS}}))
