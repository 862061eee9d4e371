"""Names of the fused sample-scan kernels (kernels/agg_scan.py) as they
appear among a profiler trace's device operations (the jitted wrapper's
name inside the HLO instruction name, e.g.
`%vmap_jit_agg_scan_fused_pallas__.2`): every operation whose name holds one
of these is sample-scan kernel time."""
SCAN_KERNELS = ("agg_scan_fused_pallas", "quantile_scan_pallas")
