"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels run in interpret mode — the kernel body
executes in Python for correctness validation; on TPU the same call sites
compile to Mosaic. `INTERPRET` flips automatically off on TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.estimators import GroupedMoments
from repro.kernels.agg_scan import (agg_scan_batched_pallas,
                                    agg_scan_fused_pallas, agg_scan_pallas,
                                    quantile_scan_pallas)
from repro.kernels.weighted_sum import weighted_sum_pallas

INTERPRET = jax.default_backend() != "tpu"


def agg_scan(values: jax.Array, rates: jax.Array, mask: jax.Array,
             group_codes: jax.Array, n_groups: int) -> GroupedMoments:
    """Fused predicate+HT grouped moments — drop-in replacement for
    estimators.grouped_moments (executor's use_pallas path)."""
    out = agg_scan_pallas(values, rates, mask, group_codes, n_groups,
                          interpret=INTERPRET)
    return GroupedMoments(n=out[0], wsum=out[1], wxsum=out[2], wx2sum=out[3],
                          var_count=out[4], var_sum=out[5], var_sum2=out[6])


def agg_scan_batched(values: jax.Array, freq: jax.Array, entry_key: jax.Array,
                     atom_cols: jax.Array, group_codes: jax.Array,
                     ks: jax.Array, pred_consts: jax.Array, ops_struct,
                     n_groups: int) -> GroupedMoments:
    """Q-query shared scan (executor's batched use_pallas path): one pass over
    the family prefix serves all Q same-template queries. Leaves are [Q, G]."""
    out = agg_scan_batched_pallas(values, freq, entry_key, atom_cols,
                                  group_codes, ks, pred_consts,
                                  ops_struct=ops_struct, n_groups=n_groups,
                                  interpret=INTERPRET)
    return GroupedMoments(n=out[:, 0], wsum=out[:, 1], wxsum=out[:, 2],
                          wx2sum=out[:, 3], var_count=out[:, 4],
                          var_sum=out[:, 5], var_sum2=out[:, 6])


def agg_scan_fused(values: jax.Array, unit: jax.Array, strat: jax.Array,
                   freq_table: jax.Array, valid: jax.Array, atom_cols,
                   group_codes: jax.Array, ks: jax.Array,
                   pred_consts: jax.Array, ops_struct, atom_slots,
                   n_groups: int, row_spans=None) -> GroupedMoments:
    """Memory-lean Q-query shared scan: streams the primitive striped layout
    (unit/strat/valid + narrow-dtype columns) and derives HT state in VMEM
    from the resident freq table, for families of up to MAX_FUSED_STRATA
    strata (every int16 stratum id), visiting only the row blocks that
    meet `row_spans` (None: every block). Leaves are [Q, G]."""
    out = agg_scan_fused_pallas(values, unit, strat, freq_table, valid,
                                atom_cols, group_codes, ks, pred_consts,
                                ops_struct=ops_struct, atom_slots=atom_slots,
                                n_groups=n_groups, interpret=INTERPRET,
                                row_spans=row_spans)
    return GroupedMoments(n=out[:, 0], wsum=out[:, 1], wxsum=out[:, 2],
                          wx2sum=out[:, 3], var_count=out[:, 4],
                          var_sum=out[:, 5], var_sum2=out[:, 6])


def quantile_scan(values: jax.Array, unit: jax.Array, strat: jax.Array,
                  freq_table: jax.Array, valid: jax.Array, atom_cols,
                  group_codes: jax.Array, k: jax.Array, lo: jax.Array,
                  hi: jax.Array, pred_consts: jax.Array, ops_struct,
                  atom_slots, n_groups: int, n_bins: int, row_spans=None
                  ) -> tuple[GroupedMoments, jax.Array]:
    """One-pass QUANTILE scan: (GroupedMoments [G], hist f32[n_bins, G]),
    its row grid bounded by `row_spans` as in agg_scan_fused."""
    mom, hist = quantile_scan_pallas(values, unit, strat, freq_table, valid,
                                     atom_cols, group_codes, k, lo, hi,
                                     pred_consts, ops_struct=ops_struct,
                                     atom_slots=atom_slots, n_groups=n_groups,
                                     n_bins=n_bins, interpret=INTERPRET,
                                     row_spans=row_spans)
    return GroupedMoments(n=mom[0], wsum=mom[1], wxsum=mom[2], wx2sum=mom[3],
                          var_count=mom[4], var_sum=mom[5],
                          var_sum2=mom[6]), hist


def weighted_sum(values: jax.Array, weights: jax.Array,
                 mask: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    return weighted_sum_pallas(values, weights, mask, interpret=INTERPRET)
