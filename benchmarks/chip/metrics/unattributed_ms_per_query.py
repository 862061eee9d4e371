"""Front end: per answered query, its traced time less the union of every
span under the root, in ms, averaged: the time no span explains. Program
spans."""
import numpy as np

from blinkbench.devtrace import clip, union
from blinkbench.spans import trace_of


def read(run):
    out = []
    for r in run.answered:
        tr = trace_of(r)
        if tr is None:
            continue
        lo, hi = tr.t0, tr.t0 + tr.total_s
        covered = union(clip([(s.t0, s.t1) for s in tr.spans
                              if s.parent >= 0], lo, hi))
        out.append((hi - lo - sum(e - s for s, e in covered)) * 1e3)
    return float(np.mean(out)) if out else None
