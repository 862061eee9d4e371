"""Readings of the check and of its lower-precision control, seed by seed.

    python3 benchmarks/chip/tools/control.py --workload <cell> \
        --seconds <s> --seeds 11,12,13

Runs the cell as run.py does once per seed, in this one process, and after
each run's own check also puts the reference computed at bfloat16 and at
`high` (a bfloat16 pair) in the program's place on the same sampled
answers, and judges it as the check judges the program: the control
readings the check's limits are set against, each with its verdict. Each
run's result line goes to standard output; the control readings to
standard error. The first seed's set-up counts from the process's start,
as run.py's does.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from blinkbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    rc, t_start = 0, T_START
    for seed in args.seeds.split(","):
        rc = max(rc, harness.main(
            ["--workload", args.workload, "--seed", seed, "--seconds",
             args.seconds, "--trace", "0"], t_start=t_start,
            controls=("bf16", "high")))
        t_start = time.monotonic()
    return rc


if __name__ == "__main__":
    sys.exit(main())
