"""Planner: mean `plan` stage time per answered query in ms (family
selection with its probe scans, the ELP and the error-bound pilots).
Program spans, through `Answer.timings`."""
import numpy as np


def read(run):
    out = [r.answer.timings["plan"] * 1e3 for r in run.answered
           if r.answer.timings and "plan" in r.answer.timings]
    return float(np.mean(out)) if out else None
