"""Kernel: the fused sample-scan kernels' share of their HBM roofline, in %.

The least time the chip could take is the bytes the answers required
(widths.required_bytes: K-prefix rows x least-width value, stratum and
predicate/group columns) over the device's peak HBM bandwidth; bytes, not
operations, bound this scan (a handful of flops per byte). The share is
that time over the kernels' device time in the traced part of the window,
for the sampled answers finished in it. Device trace."""
from blinkbench.kernels import SCAN_KERNELS


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.kernel_ns(SCAN_KERNELS)
    need = sum(run.required_bytes(r) for r in run.in_traced())
    if not ns or not need:
        return None
    return 100.0 * need / run.peaks()["hbm_bytes_per_s"] / (ns * 1e-9)
