"""A run of a looped cell that also reads the controls: what a program
computing below the stated precision, running one pass fewer, or keeping one
K/V plane a layer would have served at the same positions
(``harness/looped.py`` with ``harness/looped_reference.py``), their numbers
beside the run's own in ``compared`` as ``control_<mode>_*`` (held to
nothing).  The limits in the configuration's file are set between the
readings.

    python3 benchmark/calibrate_looped.py --workload <cell> --seed <n>
        --seconds <s> --trace 0

And the two roofline shares that PERF.md section 7 defines for the cell and
the manifest has no room for, by hand from the result line of a TRACED run
(a file holding that line) and ``harness/looped_costs.py``:

    python3 benchmark/calibrate_looped.py --by-hand <file> --workload <cell>
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run                                     # noqa: E402
from harness import device, looped_costs, looped_reference  # noqa: E402


def by_hand(line: dict, cfg: dict) -> dict:
    """``decode_step_roofline.ouro`` and ``paged_calls_roofline.ouro`` in %,
    from a traced result line: the least bytes (``looped_costs``) at the
    window's mean live table entries, over the chip's HBM bandwidth, over
    the step's mean device time and over the share of it spent in the
    paged calls."""
    m = {k: v["value"] for k, v in line["metrics"].items()}
    e = cfg["engine"]
    table = e["slots"] * -(-cfg["max_len"] // e["block_size"])
    live = m["live_block_share.jamba"] / 100.0 * table
    per_s = device.peaks(line["device"]["kind"])["hbm_bytes_per_s"]
    step_s = m["decode_step_ms.jamba"] / 1e3
    calls_s = step_s * m["paged_attn_share.jamba"] / 100.0
    return {
        "live_blocks": live,
        "decode_step_roofline.ouro": 100.0 * looped_costs
        .decode_step_min_bytes(cfg, live, 2) / per_s / step_s,
        "paged_calls_roofline.ouro": 100.0 * looped_costs
        .paged_calls_min_bytes(cfg, live) / per_s / calls_s}


if __name__ == "__main__":
    if "--by-hand" in sys.argv:
        path = sys.argv[sys.argv.index("--by-hand") + 1]
        cell = sys.argv[sys.argv.index("--workload") + 1]
        with open(path) as f:
            line = json.loads(f.read().strip().splitlines()[-1])
        print("[by_hand] " + json.dumps(
            by_hand(line, bench_run.load_cell(cell)["cfg"])))
        sys.exit(0)
    sys.exit(bench_run.main(
        hooks={"measure": {"controls": looped_reference.CONTROLS}}))
