"""Front end: per answered query, its `admit.queue` span (from submission
until the dispatcher took it off the queue), in ms, averaged. Program
spans."""
from blinkbench.spans import mean_over_answers, total_ms


def read(run):
    return mean_over_answers(run, "admit.queue", total_ms)
