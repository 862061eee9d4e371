"""Run one benchmark cell once, on the accelerator this machine holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cells, metrics and bounds are in BENCHMARK.json at the repository's
root; the harness is blinkbench/harness.py. The last line of standard
output is the run's result as one JSON object. Without a TPU (or with
fewer chips than the cell asks for) the run exits non-zero and prints no
result.
"""
import time

T_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from blinkbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
