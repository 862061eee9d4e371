"""Device: per query answered in the traced part of the window, the time
device 0 sat idle inside the union of its `scan.launch` and `scan.wait`
spans, in ms, averaged: the host part of its scans. Spans are put on the
profiler's clock by the window's start mark, as the harness puts them.
Device trace."""
import numpy as np

from blinkbench.devtrace import union
from blinkbench.spans import overlap, trace_of

SCAN_HOST = ("scan.launch", "scan.wait")


def read(run):
    if run.trace is None:
        return None
    offset_ns = run.trace.mark_ns - run.traced[0] * 1e9
    found, out = False, []
    for r in run.in_traced():
        tr = trace_of(r)
        if tr is None:
            continue
        scans = union([(s.t0 * 1e9 + offset_ns, s.t1 * 1e9 + offset_ns)
                       for s in tr.spans if s.name in SCAN_HOST])
        found = found or bool(scans)
        out.append(overlap(scans, run.trace.gaps) * 1e-6)
    return float(np.mean(out)) if found else None
