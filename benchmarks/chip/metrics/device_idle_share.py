"""Device: share of the traced part of the window in which no operation
ran on the chip (1 - busy union / window). Device trace."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
