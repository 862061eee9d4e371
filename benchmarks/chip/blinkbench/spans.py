"""The program's spans in the answers, for the per-layer readers.

Each answered query carries its trace (`Answer.trace`) in a `--trace 1`
run: spans with a name, a parent index (-1 for the root, `request`) and
start and end on the harness's monotonic clock. A reader whose spans the
program does not record reads None, not 0.
"""
from __future__ import annotations

import numpy as np


def trace_of(rec):
    return getattr(rec.answer, "trace", None)


def mean_over_answers(run, name: str, value) -> float | None:
    """Mean over the answered queries that carry a trace of
    `value(spans)`, `spans` being the trace's spans called `name`; None
    where no trace holds such a span."""
    found, out = False, []
    for r in run.answered:
        tr = trace_of(r)
        if tr is None:
            continue
        spans = [s for s in tr.spans if s.name == name]
        found = found or bool(spans)
        out.append(value(spans))
    return float(np.mean(out)) if found else None


def total_ms(spans) -> float:
    return 1e3 * sum(s.t1 - s.t0 for s in spans)


def overlap(a, b) -> float:
    """Length of the intersection of two lists of disjoint ascending
    intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
