"""A run of a block-diffusion cell that also reads the control: the
reference in the nearest precision below the one the configuration states
(fp8 for bfloat16), through the same replay, its numbers beside the run's
own in ``compared`` as ``control_fp8_*`` (held to nothing).  The limits in
the configuration's file are set between the two readings.

    python3 benchmark/calibrate_blockgen.py --workload <cell> --seed <n>
        --seconds <s> --trace 0
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run                                     # noqa: E402

if __name__ == "__main__":
    sys.exit(bench_run.main(hooks={"measure": {"control": True}}))
