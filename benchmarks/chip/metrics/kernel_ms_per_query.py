"""Kernel: device time of the fused sample-scan kernels in the traced part
of the window, per query answered in it, in ms. Device trace."""
from blinkbench.kernels import SCAN_KERNELS


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.kernel_ns(SCAN_KERNELS)
    n = len(run.in_traced())
    return ns * 1e-6 / n if ns and n else None
