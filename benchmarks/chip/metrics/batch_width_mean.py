"""Front end: mean requests per coalesced engine call over the window,
from the change of the program's `service_batch_width` histogram."""


def read(run):
    n = run.counters["batch_width_n"]
    return run.counters["batch_width_sum"] / n if n else None
