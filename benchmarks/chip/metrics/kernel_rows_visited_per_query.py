"""Kernels: rows the scan programs' grids visited per answered query, the
`rows_visited` attribute of each `scan.launch` span in its trace (whole
kernel row blocks of the key prefix the scan's K needs, the whole striped
block on the jnp path, the table's rows for an exact scan), summed, then
averaged over the answered queries that carry a trace. Program spans.
None where no `scan.launch` carries the attribute."""
import numpy as np

from blinkbench.spans import trace_of


def read(run):
    found, out = False, []
    for r in run.answered:
        tr = trace_of(r)
        if tr is None:
            continue
        rows = [s.attrs["rows_visited"] for s in tr.spans
                if s.name == "scan.launch" and "rows_visited" in s.attrs]
        found = found or bool(rows)
        out.append(sum(rows))
    return float(np.mean(out)) if found else None
