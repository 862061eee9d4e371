"""A benchmark run that keeps its profiler trace and describes it.

    python3 benchmarks/chip/tools/probe.py OUT_DIR --workload <cell> \
        --seed <n> --seconds <s> --trace 1

Runs the cell as run.py does, copies the window's .xplane.pb into OUT_DIR
and prints, on standard error, each trace plane with its lines, event
counts and the most frequent event names: what to look at before changing
the trace reduction.
"""
import time

T_START = time.monotonic()

import collections  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from blinkbench import harness  # noqa: E402


def describe(path: Path) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            ev = list(line.events)
            span = (f" [{ev[0].start_ns:.0f}, {ev[-1].start_ns:.0f}] ns"
                    if ev else "")
            print(f"{plane.name} | {line.name}: {len(ev)} events{span}; "
                  f"top {names.most_common(8)}", file=sys.stderr)


if __name__ == "__main__":
    out = Path(sys.argv[1])
    rc = harness.main(sys.argv[2:], t_start=T_START, keep_trace=out)
    for f in sorted(out.glob("*.xplane.pb")):
        describe(f)
    sys.exit(rc)
