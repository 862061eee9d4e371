"""Executor: per answered query, the summed `scan.launch` spans of its
trace (the call into each compiled scan program until it returns:
argument handling, allocation, enqueue), in ms, averaged. Program spans."""
from blinkbench.spans import mean_over_answers, total_ms


def read(run):
    return mean_over_answers(run, "scan.launch", total_ms)
