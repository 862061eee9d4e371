"""Offered-load sweep of an open-loop cell, to find its knee.

    python3 benchmarks/chip/tools/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.5,1,2

Sets the cell up once, then serves one window per rate and prints one JSON line per rate: p50 and p90 latency, the share of
queries that met their bound, and the backlog: queries still unanswered
when the window closed, and how much later the second half of the window's
queries finished than the first (a growing queue). The knee is the
highest rate at which 90% of queries meet their bound without a growing
backlog.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from blinkbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(harness.ROOT, args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    from repro.launch import compile_cache
    compile_cache.enable()
    prep = harness.prepare(cell, args.seed, args.seconds)
    for rate in (float(r) for r in args.rates.split(",")):
        prep.mix.spec = {**prep.mix.spec, "rate_qps": rate}
        with tempfile.TemporaryDirectory() as tmp:
            got = harness.serve(prep, args.seconds, False, Path(tmp))
        recs = got.records
        close = got.window[1]
        lat = [harness.latency_s(r) for r in recs]
        half = len(lat) // 2
        e2e = harness.end_to_end(recs, 0.0)
        print(json.dumps({
            "rate_qps": rate, "queries": len(recs),
            "p50_ms": harness._finite(e2e["latency_p50_ms"]),
            "p90_ms": harness._finite(e2e["latency_p90_ms"]),
            "bound_met_share": e2e["bound_met_share"],
            "pending_at_close": sum(1 for r in recs
                                    if r.done is None or r.done > close),
            "late_half_minus_early_half_ms": harness._finite(
                1e3 * (float(np.median(lat[half:]))
                       - float(np.median(lat[:half])))) if half else 0.0,
            "engine_calls": got.counters["batch_width_n"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
