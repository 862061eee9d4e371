"""Planner: per answered query, the number of `scan.wait` spans in its
trace, i.e. host-device round trips at scans (probes, pilots, escalations,
final and exact scans, each shard attempt), averaged. Program spans."""
from blinkbench.spans import mean_over_answers


def read(run):
    return mean_over_answers(run, "scan.wait", len)
