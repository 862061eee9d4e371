"""Executor: per answered query, the summed `scan.wait` spans of its
trace (the host blocked until a scan program's outputs are ready), in ms,
averaged. Program spans."""
from blinkbench.spans import mean_over_answers, total_ms


def read(run):
    return mean_over_answers(run, "scan.wait", total_ms)
