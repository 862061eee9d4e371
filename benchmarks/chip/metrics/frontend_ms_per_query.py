"""Front end (parser, admission, queue, batching window): per answered
query, its traced time less the union of its engine spans (plan, scan,
estimate and their children), in ms, averaged. Program spans."""
import numpy as np

from blinkbench.devtrace import union

ENGINE = ("plan", "scan", "estimate")


def read(run):
    out = []
    for r in run.answered:
        tr = getattr(r.answer, "trace", None)
        if tr is None:
            continue
        engine = union([(s.t0, s.t1) for s in tr.spans
                        if s.name.split(".", 1)[0] in ENGINE])
        out.append((tr.total_s - sum(e - s for s, e in engine)) * 1e3)
    return float(np.mean(out)) if out else None
