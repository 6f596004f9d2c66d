"""A run of a CCA cell that also reads the controls: what a program computing
below the stated precision, or leaving the mixing along the sequence out,
would have served at the same positions (``harness/cca.py`` with
``harness/cca_reference.py``: matmul operands through fp8; q and k straight
from their latents and both value halves from the current token), their
numbers beside the run's own in ``compared`` as ``control_<mode>_*`` (held to
nothing).  The limits in the configuration's file are set between the
readings.

    python3 benchmark/calibrate_cca.py --workload <cell> --seed <n>
        --seconds <s> --trace 0
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run                                     # noqa: E402

if __name__ == "__main__":
    sys.exit(bench_run.main(
        hooks={"measure": {"controls": ("fp8", "no_mix")}}))
