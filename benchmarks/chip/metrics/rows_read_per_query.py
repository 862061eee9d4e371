"""Planner: rows scanned per answered query over the window (probes,
pilots, escalations and exact scans included), from the change of the
program's `engine_rows_read_total` counter."""


def read(run):
    n = len(run.answered)
    return run.counters["rows_read"] / n if n else None
