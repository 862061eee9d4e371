"""Executor: mean `scan` stage time per answered query in ms, the host
clock around dispatch and `block_until_ready` of every scan the query ran
(probes and pilots too). Program spans, through `Answer.timings`."""
import numpy as np


def read(run):
    out = [r.answer.timings.get("scan", 0.0) * 1e3 for r in run.answered
           if r.answer.timings]
    return float(np.mean(out)) if out else None
