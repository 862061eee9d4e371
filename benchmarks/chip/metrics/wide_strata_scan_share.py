"""Kernels: of the answered queries' `scan.launch` + `scan.wait` time,
the share spent on sample families of more than 4,096 strata, read from
each span's `strata` attribute (the scanned family's stratum count).
None where no span carries the attribute. Program spans."""
from blinkbench.spans import trace_of

SCAN_HOST = ("scan.launch", "scan.wait")
WIDE = 4096


def read(run):
    wide = total = 0.0
    found = False
    for r in run.answered:
        tr = trace_of(r)
        if tr is None:
            continue
        for s in tr.spans:
            if s.name in SCAN_HOST and "strata" in s.attrs:
                found = True
                total += s.t1 - s.t0
                if s.attrs["strata"] > WIDE:
                    wide += s.t1 - s.t0
    return wide / total if found and total > 0 else None
