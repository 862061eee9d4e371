"""Planner: the accuracy the ELP buys under `WITHIN n SECONDS`, the median
over time-bound answers of each answer's median group CI half-width over
|estimate|, in %. From the answers the program returns."""
from blinkbench.harness import timebound_halfwidth_pct


def read(run):
    return timebound_halfwidth_pct(run.records)
