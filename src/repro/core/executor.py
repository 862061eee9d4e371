"""Distributed query execution over sample families.

A query executes as ONE fused pass over the prefix S(φ, K) of a materialized
family: predicate evaluation → HT weighting → grouped segment reduction of the
sufficient statistics (GroupedMoments). On a mesh the prefix rows are
round-robin striped over the `data` axis (every shard holds an equal slice of
*every* prefix — DESIGN.md §2) and the per-shard partials are `psum`'d; on a
single device the same code runs without the shard_map wrapper.

The per-shard inner loop has two interchangeable implementations:
  * `ref` — pure jnp (jax.ops.segment_sum), the oracle;
  * `pallas` — the fused VMEM-tiled scan kernel (kernels/agg_scan.py).

Batched shared-scan execution
-----------------------------

`make_batched_query_fn` is the multi-query sibling of `make_query_fn`: Q
concurrent queries that share ONE template (same predicate structure, value
column, group column) execute as a single fused pass over the family prefix.
Per-query state is two traced stacks — resolution caps ks[Q] and predicate
constants pred_consts[Q, n_atoms] in flattened template order — so one
compiled program serves every batch of every instantiation of the template.
On a mesh the whole batch is merged with ONE psum of the stacked [7, Q, G]
statistics tensor; on the pallas path the per-shard scan is the fused
memory-lean kernel (kernels/agg_scan.py `agg_scan_fused_pallas`). The
(table, family, template) grouping contract that feeds this layer is
documented in docs/BATCHING.md.

Memory-lean striped layout
--------------------------

The striped block stores ONLY the sampling primitives: per-row uniform
`unit` (f32), stable stratum id `strat` (narrowest int that fits the
stratum count), the per-stratum frequency table (f32[D], tiny), a `valid`
bitmask, and dictionary-encoded data columns at their natural int8/int16
width. The derived HT state — freq = freq_table[strat] and
entry_key = unit·freq — is NOT materialized: every scan (jnp or Pallas)
re-derives it on the fly, in VMEM on the kernel path. That removes ~8
bytes/row of device memory and two full-width HBM streams per scan, and
append/tombstone epochs stop rebuilding derived arrays (the refresh is just
the delta scatter plus shipping the new frequency table). Padding and ghost
slots self-exclude through unit=+inf ⇒ entry_key=+inf, exactly as the old
stored-entry_key layout did.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import estimators as est_lib
from repro.core.sampling import SampleFamily, count_below
from repro.core.types import (AggOp, Atom, CmpOp, Conjunction, Predicate,
                              cmp_fns)
from repro.fault import inject
from repro.fault.inject import AllShardsLostError, FaultError, ShardScanError
from repro.obs import trace as obs_trace

_CMP = cmp_fns()


@dataclasses.dataclass(frozen=True)
class BoundAtom:
    """Atom with its value encoded to device-comparable form."""
    column: str
    op: CmpOp
    encoded: float


def bind_predicate(pred: Predicate, encode) -> tuple[tuple[BoundAtom, ...], ...]:
    """Encode predicate constants via `encode(column, value) -> float`."""
    return tuple(
        tuple(BoundAtom(a.column, a.op, float(encode(a.column, a.value)))
              for a in conj.atoms)
        for conj in pred.disjuncts)


def predicate_mask(columns: dict[str, jax.Array],
                   bound: tuple[tuple[BoundAtom, ...], ...]) -> jax.Array:
    """Evaluate a DNF predicate over column arrays -> bool[n]."""
    any_col = next(iter(columns.values()))
    disj = jnp.zeros(any_col.shape, dtype=bool)
    for conj in bound:
        m = jnp.ones(any_col.shape, dtype=bool)
        for a in conj:
            col = columns[a.column]
            m = m & _CMP[a.op](col.astype(jnp.float32), a.encoded)
        disj = disj | m
    return disj


# ---------------------------------------------------------------------------
# Single-shard fused pass (reference implementation; Pallas path in kernels/)
# ---------------------------------------------------------------------------

def scan_moments(columns: dict[str, jax.Array], freq: jax.Array,
                 bound_pred: tuple[tuple[BoundAtom, ...], ...],
                 value_col: str | None, group_col: str | None, n_groups: int,
                 k: float, prefix_mask: jax.Array,
                 *, use_pallas: bool = False) -> est_lib.GroupedMoments:
    """One fused scan over (a shard of) a family prefix."""
    mask = predicate_mask(columns, bound_pred) & prefix_mask
    rates = jnp.minimum(1.0, k / freq)
    values = (columns[value_col].astype(jnp.float32)
              if value_col is not None else jnp.ones_like(freq))
    gcodes = (columns[group_col].astype(jnp.int32)
              if group_col is not None else jnp.zeros(freq.shape, jnp.int32))
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.agg_scan(values, rates, mask, gcodes, n_groups)
    return est_lib.grouped_moments(values, rates, mask, gcodes, n_groups)


def _merge_psum(mom: est_lib.GroupedMoments, axes) -> est_lib.GroupedMoments:
    return jax.tree.map(lambda x: jax.lax.psum(x, axes), mom)


# ---------------------------------------------------------------------------
# Striped (distributed) family layout
# ---------------------------------------------------------------------------

# Shape-class granularity of the striped layout: local rows are padded up to
# a multiple of _STRIPE_BLOCK with _STRIPE_HEADROOM slack so small appends
# land in pre-allocated padding and keep every AOT-compiled program valid
# (docs/MAINTENANCE.md). Padded/ghost rows self-exclude: entry_key >= K_1.
_STRIPE_BLOCK = 64
_STRIPE_HEADROOM = 0.25
_STRATA_BLOCK = 128     # freq-table length granularity (new strata are rare)


@dataclasses.dataclass
class StripedFamily:
    """A SampleFamily striped round-robin over data shards.

    Row j of the family lives at shard (j % S), local index (j // S); every
    shard holds an equal slice of every prefix: balanced load for every
    resolution. The block over-allocates (_STRIPE_HEADROOM) so append deltas
    slot into existing padding, and stores ONLY the per-row sampling
    PRIMITIVES — unit u, stable stratum id, validity — plus the tiny
    per-stratum frequency table. The derived HT state (freq =
    freq_table[strat], entry_key = unit·freq) is re-derived by every scan
    (in VMEM on the kernel path), never materialized: an append ships just
    the delta rows and the refreshed frequency table.
    """
    phi: tuple[str, ...]
    ks: tuple[float, ...]
    columns: dict[str, jax.Array]   # [S, n_local]; dict-coded cols int8/int16
    valid: jax.Array                # bool[S, n_local] (padding mask)
    unit: jax.Array                 # f32[S, n_local], +inf on padding/ghosts
    strat: jax.Array                # int8/int16/int32[S, n_local] stratum ids
    freq_table: jax.Array           # f32[D_padded] per-stratum F
    n_rows: int                     # occupied slots (incl. self-excluded ghosts)
    table_rows: int
    n_shards: int
    # Host mirror: physical base-row id per occupied slot, in linear slot
    # order (slot j ↔ shard j%S, local j//S). -1 marks slots already ghosted
    # by a tombstone (so re-deletes can't double-count). Tombstones resolve
    # their scatter indices against this without any device read-back.
    slot_row_ids: np.ndarray | None = None
    # Self-excluded slots: rescale ghosts (rows pushed past K₁ by a merge)
    # plus tombstoned rows. Drives the compaction trigger.
    n_ghosts: int = 0
    # Row-grid bound (row_spans): the first `n_sorted` slots are in entry-key
    # order, and key_floor[j] is a non-decreasing lower bound of slot j's
    # key unit·freq_table[strat] as the kernels compute it, taken when the
    # block was striped. A tombstone (unit := +inf) and an append (F only
    # grows, sampling.merge_family) only raise a slot's key, so the floor
    # holds until the next restripe; appended slots lie past n_sorted, out
    # of key order, and every scan visits them.
    key_floor: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.float32))
    n_sorted: int = 0

    @property
    def capacity(self) -> int:
        return self.n_shards * int(self.unit.shape[1])

    @property
    def n_local(self) -> int:
        return int(self.unit.shape[1])

    @property
    def ghost_fraction(self) -> float:
        """Fraction of occupied slots that are self-excluded ghosts — the
        scan-efficiency loss a compacting restripe reclaims."""
        return self.n_ghosts / max(self.n_rows, 1)

    @property
    def shape_class(self) -> tuple:
        """Everything an AOT-compiled program's input signature depends on.
        Appends that keep this unchanged reuse compiled programs as-is.
        Narrow column/strat dtypes and the padded freq-table length are part
        of the signature now that programs take the primitive layout."""
        return (self.n_shards, int(self.unit.shape[1]),
                tuple(sorted((c, str(a.dtype))
                             for c, a in self.columns.items())),
                str(self.strat.dtype), int(self.freq_table.shape[0]))


def _padded_local(n: int, n_shards: int) -> int:
    n_local = -(-max(n, 1) // n_shards)
    n_local = int(n_local * (1.0 + _STRIPE_HEADROOM)) + 1
    return -(-n_local // _STRIPE_BLOCK) * _STRIPE_BLOCK


def _padded_freq_table(freq_table: np.ndarray) -> np.ndarray:
    want = -(-max(len(freq_table), 1) // _STRATA_BLOCK) * _STRATA_BLOCK
    out = np.ones(want, dtype=np.float32)
    out[: len(freq_table)] = freq_table
    return out


def _narrow_int_dtype(a: np.ndarray) -> np.dtype:
    """Smallest of int8/int16/int32 holding every value — the dtype-selection
    rule for dictionary-encoded columns and stratum ids (docs/BATCHING.md).
    The scan kernels stream columns at this width and widen in VMEM; an
    append whose delta overflows the chosen width forces a full restripe
    (stripe_append returns None), which re-picks widths from the new data."""
    if a.size == 0:
        return np.dtype(np.int8)
    lo, hi = int(a.min()), int(a.max())
    for dt in (np.int8, np.int16):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return np.dtype(dt)
    return np.dtype(np.int32)


def _storage_dtype(a: np.ndarray) -> np.dtype:
    """Device storage dtype for a data column: ints narrow per
    _narrow_int_dtype, floats stream as f32, anything else unchanged."""
    if a.dtype.kind in "iu":
        return _narrow_int_dtype(a)
    if a.dtype.kind == "f":
        return np.dtype(np.float32)
    return a.dtype


def _fits_dtype(a, dtype) -> bool:
    """Do the (integer) values fit the narrow storage dtype?"""
    dt = np.dtype(dtype)
    a = np.asarray(a)
    if dt.kind not in "iu" or a.size == 0:
        return True
    if a.dtype.kind not in "iu":
        a = a.astype(np.int64)
    info = np.iinfo(dt)
    return bool(a.min() >= info.min and a.max() <= info.max)


def _replicated(striped: StripedFamily):
    """Placement for small per-epoch payloads (scatter indices, delta rows,
    the freq table): a full copy on every device of the block's mesh, or the
    default device when the block is not sharded."""
    sh = striped.unit.sharding
    return NamedSharding(sh.mesh, P()) if isinstance(sh, NamedSharding) else None


def stripe_family(fam: SampleFamily, n_shards: int,
                  min_local: int | None = None, *, mesh: Mesh | None = None,
                  data_axes: tuple[str, ...] = ("data",)) -> StripedFamily:
    """Stripe on host, then move the WHOLE padded block with one device_put.

    Pad+reshape stays in NumPy (no per-column host→device round trips); the
    single device_put of the column pytree lets the runtime batch every
    buffer into one transfer, so (re)striping a wide family doesn't
    serialize on per-column memcpys.

    `min_local` pins the per-shard slot count to at least that value: a
    COMPACTING restripe (ghost/tombstone reclamation) passes the old block's
    n_local so the rebuilt block keeps the same shape class and every
    AOT-compiled program stays valid — the family only ever shrinks under
    compaction, so the old geometry always fits.

    With a `mesh`, row s of every [S, n_local] array is placed on the device
    that owns shard s of `data_axes` (the layout the shard_map scans take),
    and the freq table is replicated; without one the block goes to the
    default device.
    """
    n = fam.n_rows
    n_local = _padded_local(n, n_shards)
    if min_local is not None:
        n_local = max(n_local, int(min_local))
    pad = n_local * n_shards - n

    def stripe(arr, fill, dtype=None):
        a = np.asarray(arr)
        if dtype is not None:
            a = a.astype(dtype)
        if pad:
            a = np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        return np.ascontiguousarray(a.reshape(n_local, n_shards).T)  # [S, n_local]

    # Read host mirrors wherever they exist (the family's own device arrays
    # are LAZY — sampling._LazyFamilyColumns — and the striping pass must not
    # be what materializes them; gathered join columns have no host mirror
    # and fall back to a device read, exactly as before).
    strat = (fam.row_strata if fam.row_strata is not None
             else np.zeros(n, dtype=np.int64))
    if fam.unit_host is not None:
        unit = fam.unit_host
    elif fam.unit is not None:   # legacy eagerly-built family
        unit = np.asarray(fam.unit)
    else:                        # derive from the legacy stored entry keys
        entry_key = (fam.entry_key_host if fam.entry_key_host is not None
                     else np.asarray(fam.entry_key))
        freq = (fam.stratum_freqs.astype(np.float32)[fam.row_strata]
                if fam.row_strata is not None else np.asarray(fam.freq))
        unit = entry_key / np.maximum(freq, 1e-30)
    # Packed narrow dtypes: dictionary-encoded columns and stratum ids are
    # stored (and later STREAMED by the kernels) at the smallest int width
    # that holds their dictionary; fill 0 always fits. Derived freq/
    # entry_key are NOT materialized — scans re-derive them from
    # (unit, strat, freq_table).
    host_block = {
        "cols": {c: stripe(a, 0, _storage_dtype(a))
                 for c, a in ((c, np.asarray(fam.host_column(c)))
                              for c in fam.columns)},
        "valid": stripe(np.ones(n, dtype=bool), False),
        "unit": stripe(unit.astype(np.float32), np.inf),
        "strat": stripe(strat, 0, _narrow_int_dtype(np.asarray(strat))),
        "freq_table": _padded_freq_table(
            fam.stratum_freqs.astype(np.float32)),
    }
    placement = None
    if mesh is not None:
        rows = NamedSharding(mesh, P(data_axes))
        placement = {"cols": {c: rows for c in host_block["cols"]},
                     "valid": rows, "unit": rows, "strat": rows,
                     "freq_table": NamedSharding(mesh, P())}
    dev = jax.device_put(host_block, placement)
    slot_row_ids = (fam.row_ids.astype(np.int64).copy()
                    if fam.row_ids is not None
                    else np.full(n, -1, dtype=np.int64))
    # The kernels' f32 key of every slot; a suffix minimum makes a floor
    # that is non-decreasing even where the keys are not.
    keys = (unit.astype(np.float32)
            * host_block["freq_table"][np.asarray(strat, dtype=np.int64)])
    key_floor = np.minimum.accumulate(keys[::-1])[::-1]
    return StripedFamily(fam.phi, fam.ks, dev["cols"], dev["valid"],
                         dev["unit"], dev["strat"], dev["freq_table"],
                         n, fam.table_rows, n_shards,
                         slot_row_ids=slot_row_ids, n_ghosts=0,
                         key_floor=key_floor, n_sorted=n)


def _pad_pow2(a: np.ndarray, d: int) -> np.ndarray:
    """Pad a length-d leading axis to the next power of two (min 64) by
    REPEATING the last element: duplicate writes of identical values are
    idempotent for every scatter that consumes the result, and the pow-2 pad
    classes keep the jitted scatter programs shared across epochs. One
    definition for both the append and tombstone scatters — the pad recipe
    is load-bearing for program-cache reuse and must not fork."""
    d_pad = max(64, 1 << (d - 1).bit_length())
    a = np.asarray(a)
    return np.concatenate([a, np.repeat(a[-1:], d_pad - d, axis=0)])


@jax.jit
def _scatter_refresh(cols, unit, strat, valid, payload):
    """One fused device program for an incremental restripe: scatter the
    (padded) delta rows into the block. With the memory-lean layout there is
    nothing to re-derive — every scan computes freq/entry_key from
    (unit, strat) and the shipped frequency table — so the refresh is JUST
    the delta scatter. Module-level jit + power-of-two delta padding ⇒
    compiled once per (shape class, delta pad class), reused by every
    subsequent append epoch."""
    s_idx, l_idx = payload["s"], payload["l"]

    def scatter(arr, vals):
        return arr.at[s_idx, l_idx].set(vals.astype(arr.dtype))

    cols = {c: scatter(cols[c], payload["cols"][c]) for c in cols}
    unit = scatter(unit, payload["unit"])
    strat = scatter(strat, payload["strat"])
    valid = valid.at[s_idx, l_idx].set(True)
    return cols, unit, strat, valid, payload["freq_table"]


def stripe_append(striped: StripedFamily, fam: SampleFamily,
                  block) -> StripedFamily | None:
    """Incremental restripe: scatter an append's DeltaBlock into the striped
    block's padding.

    The only host→device traffic is ONE device_put of the delta payload
    (d rows + the refreshed per-stratum frequency table); freq/entry_key are
    never materialized — scans derive them from the stored (unit, stratum)
    primitives against the NEW table, which also turns rows the rescale
    pushed past K_1 into self-excluding ghosts (entry_key >= K_1 fails every
    prefix test). The delta is padded to a power-of-two row count by
    REPEATING its last row (duplicate writes of identical values —
    idempotent), so the jitted scatter program is shared across epochs.
    Returns None when the delta outgrows the padded capacity OR overflows a
    column's narrow storage dtype — the caller falls back to a full
    restripe, which re-picks dtypes and resets the shape class.
    """
    d = block.n_rows
    start = striped.n_rows
    s_count = striped.n_shards
    if start + d > striped.capacity:
        return None
    freq_table = _padded_freq_table(block.freq_table)
    if d == 0:
        cols, unit, strat, valid = (striped.columns, striped.unit,
                                    striped.strat, striped.valid)
        ftab = jax.device_put(freq_table, _replicated(striped))
    else:
        # Narrow-dtype overflow: a delta value (or new stratum id) outside
        # the stored int8/int16 range cannot be scattered losslessly.
        if not _fits_dtype(block.strata, striped.strat.dtype):
            return None
        for c, v in block.columns.items():
            if not _fits_dtype(v, striped.columns[c].dtype):
                return None

        def pad(a):
            return _pad_pow2(a, d)

        j = np.arange(start, start + d)
        payload = {
            "s": pad((j % s_count).astype(np.int32)),
            "l": pad((j // s_count).astype(np.int32)),
            "cols": {c: pad(v) for c, v in block.columns.items()},
            "unit": pad(block.unit.astype(np.float32)),
            "strat": pad(block.strata.astype(np.int32)),
            "freq_table": freq_table,
        }
        cols, unit, strat, valid, ftab = _scatter_refresh(
            striped.columns, striped.unit, striped.strat, striped.valid,
            jax.device_put(payload, _replicated(striped)))
    old_ids = (striped.slot_row_ids if striped.slot_row_ids is not None
               else np.full(start, -1, dtype=np.int64))
    new_ids = (block.row_ids.astype(np.int64) if block.row_ids is not None
               else np.full(d, -1, dtype=np.int64))
    return StripedFamily(fam.phi, fam.ks, cols, valid, unit, strat, ftab,
                         start + d, fam.table_rows, s_count,
                         slot_row_ids=np.concatenate([old_ids, new_ids]),
                         # rows the rescale pushed past K₁ stay in the block
                         # as self-excluded ghosts until compaction
                         n_ghosts=striped.n_ghosts + block.n_dropped_old,
                         key_floor=striped.key_floor,
                         n_sorted=striped.n_sorted)


@jax.jit
def _scatter_ghost(unit, valid, s_idx, l_idx):
    """One fused device program for a tombstone pass: turn the dead rows'
    slots into self-excluding ghosts. unit := +inf makes every derived
    entry_key = unit·freq = +inf, failing every prefix test (there is no
    stored entry_key to poke anymore); valid := False covers the quantile/
    ref paths and fault-shard masks. Module-level jit + power-of-two index
    padding ⇒ compiled once per (shape class, pad class), like the append
    scatter."""
    unit = unit.at[s_idx, l_idx].set(jnp.float32(jnp.inf))
    valid = valid.at[s_idx, l_idx].set(False)
    return unit, valid


def stripe_tombstone(striped: StripedFamily, dead_row_ids: np.ndarray,
                     table_rows: int | None = None) -> StripedFamily:
    """Ghost the slots of tombstoned sampled rows — the device half of a
    delete. Ships ONLY a bitmask scatter (one f32 + one bool scatter at the
    dead slots): no column rewrite, no freq-table refresh, no re-keying —
    inclusion frequencies are untouched by deletes (sampling layer docs) —
    and the block keeps its shape class, so every AOT-compiled program stays
    valid. Slots are found via the host slot_row_ids mirror; ghosted slots
    are marked -1 there so a row can never be double-counted. `table_rows`
    is the post-mutation LIVE table count (dead_row_ids are only the dead
    rows that were SAMPLED, so it cannot be derived here)."""
    if table_rows is None:
        table_rows = striped.table_rows
    ids = striped.slot_row_ids
    if ids is None or len(dead_row_ids) == 0:
        return dataclasses.replace(striped, table_rows=table_rows)
    dead_row_ids = np.asarray(dead_row_ids, dtype=np.int64)
    slots = np.flatnonzero(np.isin(ids[: striped.n_rows], dead_row_ids))
    if slots.size == 0:
        return dataclasses.replace(striped, table_rows=table_rows)
    d = int(slots.size)
    slots_p = _pad_pow2(slots, d)
    s_idx = (slots_p % striped.n_shards).astype(np.int32)
    l_idx = (slots_p // striped.n_shards).astype(np.int32)
    unit, valid = _scatter_ghost(striped.unit, striped.valid,
                                 *jax.device_put((s_idx, l_idx),
                                                 _replicated(striped)))
    new_ids = ids.copy()
    new_ids[slots] = -1
    return dataclasses.replace(
        striped, unit=unit, valid=valid,
        slot_row_ids=new_ids, n_ghosts=striped.n_ghosts + d,
        table_rows=table_rows)


def remap_slot_row_ids(striped: StripedFamily,
                       remap: np.ndarray) -> StripedFamily:
    """Re-key the striped block's host slot_row_ids mirror through a
    base-table compaction remap (old physical id -> new id, -1 = dropped).
    Purely a host-mirror rewrite: the device arrays reference no physical
    ids, so a base compaction ships ZERO device traffic through the striped
    layer and every compiled program stays valid. Ghosted slots stay -1;
    rescale-ghost slots still name live rows and remap like occupied ones
    (a later tombstone of such a row must still find its slot)."""
    ids = striped.slot_row_ids
    if ids is None:
        return striped
    remap = np.asarray(remap, dtype=np.int64)
    new_ids = np.where(ids >= 0, remap[np.maximum(ids, 0)], -1)
    return dataclasses.replace(striped, slot_row_ids=new_ids)


def scan_args(striped: StripedFamily) -> tuple:
    """The positional tail every compiled scan program takes — the primitive
    memory-lean layout (columns, unit, strat, freq_table, valid). One
    definition so engine call sites and tests cannot drift."""
    return (striped.columns, striped.unit, striped.strat,
            striped.freq_table, striped.valid)


def row_spans(striped: StripedFamily, k: float) -> np.ndarray:
    """int32[3] (head_end, tail_start, tail_end): the local rows of each
    shard that a scan at resolution cap `k` has to visit, [0, head_end) ∪
    [tail_start, tail_end); the kernels' `row_spans`. The head covers every
    key-ordered slot whose key floor is below k rounded up to f32
    (sampling.count_below, the same rule as the rows counted read), which
    contains every slot the kernel's `unit·freq < float32(k)` can pass; the
    tail is the appended slots [n_sorted, n_rows). Slot j lives at local
    row j // S, so with S shards the head is ⌈slots / S⌉ rows."""
    s = striped.n_shards
    head_end = -(-count_below(striped.key_floor, k) // s)
    if striped.n_sorted >= striped.n_rows:
        return np.array([head_end, head_end, head_end], np.int32)
    return np.array([head_end, striped.n_sorted // s,
                     -(-striped.n_rows // s)], np.int32)


def rows_visited(striped: StripedFamily, spans: np.ndarray,
                 kernel: bool) -> int:
    """Rows a scan program's grid visits over all shards: the kernel row
    blocks that meet the spans, or the whole block on the jnp path (which
    masks rows rather than skipping them)."""
    if not kernel:
        return striped.capacity
    from repro.kernels.agg_scan import (DEFAULT_BLOCK_ROWS_BATCHED as b,
                                        visit_plan)
    steps = visit_plan(spans, -(-striped.n_local // b), b, xp=np)[2]
    return int(steps) * b * striped.n_shards


def derive_ht(unit: jax.Array, strat: jax.Array, freq_table: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """(freq, entry_key) derived from the stored sampling primitives —
    the jnp mirror of the kernels' in-VMEM derivation. Bit-identical to the
    old materialized arrays: the same f32 gather + multiply that
    _scatter_refresh used to run once per epoch, now per scan."""
    freq = freq_table[strat.astype(jnp.int32)]
    return freq, unit * freq


def run_query_striped(striped: StripedFamily, bound_pred, value_col: str | None,
                      group_col: str | None, n_groups: int, k: float,
                      mesh: Mesh | None = None, data_axes: tuple[str, ...] = ("data",),
                      use_pallas: bool = False) -> est_lib.GroupedMoments:
    """Un-jitted execution (tests / one-off). Production path: make_query_fn."""

    def shard_fn(cols, unit, strat, ftab, valid):
        freq, ek = derive_ht(unit, strat, ftab)
        prefix = valid & (ek < k)
        return scan_moments(cols, freq, bound_pred, value_col, group_col,
                            n_groups, k, prefix, use_pallas=use_pallas)

    if mesh is None:
        mom = jax.vmap(lambda c, u, s, v: shard_fn(
            c, u, s, striped.freq_table, v)
        )(striped.columns, striped.unit, striped.strat, striped.valid)
        return jax.tree.map(lambda x: x.sum(axis=0), mom)

    pspec = P(data_axes)
    fn = jax.shard_map(
        lambda c, u, s, ft, v: _merge_psum(
            jax.tree.map(lambda x: x[0],
                         jax.vmap(lambda cc, uu, ss, vv: shard_fn(
                             cc, uu, ss, ft, vv))(c, u, s, v)),
            data_axes),
        mesh=mesh,
        in_specs=(pspec, pspec, pspec, P(), pspec),
        out_specs=P(),
        check_vma=not use_pallas,
    )
    return fn(*scan_args(striped))


def pred_structure(bound: tuple[tuple[BoundAtom, ...], ...]):
    """Split a bound predicate into (static structure, traced constants):
    structure = ((column, op), ...) per conjunction; constants = matching
    nested tuple of floats. Lets ONE jitted query program serve every
    instantiation of a template (paper §2.1: template-stable workloads)."""
    struct = tuple(tuple((a.column, a.op) for a in conj) for conj in bound)
    vals = tuple(tuple(a.encoded for a in conj) for conj in bound)
    return struct, vals


def flat_atoms(struct) -> tuple[tuple[str, CmpOp], ...]:
    """Flatten a template structure to its atoms in template order — the
    canonical atom indexing shared by the batched executor and kernel."""
    return tuple((col, op) for conj in struct for (col, op) in conj)


def flatten_pred_vals(vals) -> tuple[float, ...]:
    """Nested per-conjunction constants → flat tuple in template order."""
    return tuple(v for conj in vals for v in conj)


def eval_pred(struct, cols: dict[str, jax.Array], pred_vals) -> jax.Array:
    """Evaluate a template structure with traced NESTED constants (mirrors
    pred_structure's vals layout) over column arrays -> bool[n]."""
    return eval_pred_flat(struct, cols, flatten_pred_vals(pred_vals))


def eval_pred_flat(struct, cols: dict[str, jax.Array],
                   consts: jax.Array) -> jax.Array:
    """Evaluate a template structure with traced FLAT constants consts[A]
    (flat_atoms order) over column arrays -> bool[n]."""
    any_col = next(iter(cols.values()))
    if not struct:
        return jnp.ones(any_col.shape, bool)
    disj = jnp.zeros(any_col.shape, dtype=bool)
    ai = 0
    for conj in struct:
        m = jnp.ones(any_col.shape, dtype=bool)
        for (col, op) in conj:
            m = m & _CMP[op](cols[col].astype(jnp.float32), consts[ai])
            ai += 1
        disj = disj | m
    return disj


def dedup_atom_slots(atoms) -> tuple[tuple[str, ...], tuple[int, ...]]:
    """Unique atom column names + per-atom slot mapping: the kernel streams
    each column ONCE even when the template compares it several times."""
    names: list[str] = []
    slots: list[int] = []
    for col, _ in atoms:
        if col not in names:
            names.append(col)
        slots.append(names.index(col))
    return tuple(names), tuple(slots)


def on_kernel(struct, use_pallas: bool, *, quantile: bool = False,
              mesh: Mesh | None = None) -> bool:
    """Whether a scan program built for `struct` runs the fused kernel.
    Its qconst block holds k (and, for QUANTILE, lo and hi) beside the
    predicate's constants, in CONST_LANES lanes; wider templates, and
    QUANTILE on a mesh (which psums jnp partials), run the jnp path."""
    from repro.kernels.agg_scan import CONST_LANES
    lanes = len(flat_atoms(struct)) + (3 if quantile else 1)
    return (use_pallas and lanes <= CONST_LANES
            and not (quantile and mesh is not None))


def make_query_fn(struct, value_col: str | None,
                  group_col: str | None, n_groups: int,
                  mesh: Mesh | None = None,
                  data_axes: tuple[str, ...] = ("data",),
                  use_pallas: bool = False):
    """Compile the fused query program once per (family × template).
    Returns jitted fn(k, pred_vals, cols, unit, strat, freq_table, valid,
    spans=None) -> GroupedMoments over the primitive memory-lean layout
    (scan_args order; freq/entry_key are derived in-scan). `spans`
    (row_spans) bounds the kernel's row grid; the jnp path scans every
    row whatever it says. k, the spans and the predicate constants are
    traced, so re-instantiations don't retrace — and the striped block
    itself is a TRACED ARGUMENT rather than a captured constant, so an
    incremental append that keeps the padded shape class
    (StripedFamily.shape_class) reuses the same AOT-compiled program on the
    updated arrays. The pallas path runs the fused memory-lean kernel as a
    Q=1 batch (narrow columns streamed as stored, HT state derived in
    VMEM)."""
    atoms = flat_atoms(struct)
    ops_struct = tuple(tuple(op for _, op in conj) for conj in struct)
    use_pallas = on_kernel(struct, use_pallas)
    acol_names, atom_slots = dedup_atom_slots(atoms)

    def shard_fn(k, pred_vals, spans, cols, unit, strat, ftab, valid):
        values = (cols[value_col].astype(jnp.float32)
                  if value_col is not None else jnp.ones_like(unit))
        gcodes = (cols[group_col].astype(jnp.int32)
                  if group_col is not None else jnp.zeros(unit.shape, jnp.int32))
        if use_pallas:
            from repro.kernels import ops as kops
            acols = tuple(cols[c] for c in acol_names)
            consts = (jnp.stack(list(flatten_pred_vals(pred_vals)))
                      if atoms else jnp.zeros((0,), jnp.float32))
            mom = kops.agg_scan_fused(
                values, unit, strat, ftab, valid, acols, gcodes,
                jnp.asarray(k, jnp.float32)[None], consts[None, :],
                ops_struct, atom_slots, n_groups, spans)
            return jax.tree.map(lambda x: x[0], mom)
        freq, ek = derive_ht(unit, strat, ftab)
        mask = eval_pred(struct, cols, pred_vals) & valid & (ek < k)
        rates = jnp.minimum(1.0, k / freq)
        return est_lib.grouped_moments(values, rates, mask, gcodes, n_groups)

    if mesh is None:
        def fn(k, pred_vals, cols, unit, strat, freq_table, valid,
               spans=None):
            mom = jax.vmap(lambda c, u, s, v: shard_fn(
                k, pred_vals, spans, c, u, s, freq_table, v)
            )(cols, unit, strat, valid)
            return jax.tree.map(lambda x: x.sum(axis=0), mom)
        return jax.jit(fn)

    pspec = P(data_axes)

    def fn(k, pred_vals, cols, unit, strat, freq_table, valid, spans=None):
        inner = jax.shard_map(
            lambda c, u, s, ft, v: _merge_psum(
                jax.tree.map(lambda x: x[0],
                             jax.vmap(lambda cc, uu, ss, vv: shard_fn(
                                 k, pred_vals, spans, cc, uu, ss, ft, vv)
                             )(c, u, s, v)),
                data_axes),
            mesh=mesh,
            in_specs=(pspec, pspec, pspec, P(), pspec),
            out_specs=P(),
            # pallas_call outputs carry no varying-axes annotation; the
            # psum above is what makes the result replicated.
            check_vma=not use_pallas,
        )
        return inner(cols, unit, strat, freq_table, valid)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Batched shared-scan execution (one family pass, Q same-template queries)
# ---------------------------------------------------------------------------

def make_batched_query_fn(struct,
                          value_col: str | None, group_col: str | None,
                          n_groups: int, mesh: Mesh | None = None,
                          data_axes: tuple[str, ...] = ("data",),
                          use_pallas: bool = False):
    """Compile ONE fused multi-query program per (family × template).

    Returns jitted fn(ks, pred_consts, cols, unit, strat, freq_table, valid,
    spans=None) -> GroupedMoments with leading batch axis: ks is f32[Q]
    (per-query
    resolution caps), pred_consts is f32[Q, A] (per-query predicate
    constants in flat_atoms order). Every leaf of the result is
    [Q, n_groups]. The family prefix streams from HBM once for the whole
    batch; per-query work is VPU/MXU-only. On a mesh the per-shard partials
    for ALL Q queries merge with a single psum. As with make_query_fn, the
    striped block is a traced argument so appends that preserve the padded
    shape class keep compiled programs valid. The pallas path is the fused
    memory-lean kernel: narrow columns stream as stored, the freq table is
    VMEM-resident, HT state is derived per block, and `spans` (row_spans
    at the batch's largest k) bounds its row grid.
    """
    atoms = flat_atoms(struct)
    ops_struct = tuple(tuple(op for _, op in conj) for conj in struct)
    use_pallas = on_kernel(struct, use_pallas)
    acol_names, atom_slots = dedup_atom_slots(atoms)

    def shard_fn(ks, pred_consts, spans, cols, unit, strat, ftab, valid):
        values = (cols[value_col].astype(jnp.float32)
                  if value_col is not None else jnp.ones_like(unit))
        gcodes = (cols[group_col].astype(jnp.int32)
                  if group_col is not None else jnp.zeros(unit.shape, jnp.int32))
        if use_pallas:
            from repro.kernels import ops as kops
            acols = tuple(cols[c] for c in acol_names)
            return kops.agg_scan_fused(values, unit, strat, ftab, valid,
                                       acols, gcodes, ks, pred_consts,
                                       ops_struct, atom_slots, n_groups,
                                       spans)
        freq, ek = derive_ht(unit, strat, ftab)

        def one(k, consts):
            mask = eval_pred_flat(struct, cols, consts) & valid & (ek < k)
            rates = jnp.minimum(1.0, k / freq)
            return est_lib.grouped_moments(values, rates, mask, gcodes,
                                           n_groups)
        return jax.vmap(one)(ks, pred_consts)

    if mesh is None:
        def fn(ks, pred_consts, cols, unit, strat, freq_table, valid,
               spans=None):
            mom = jax.vmap(lambda c, u, s, v: shard_fn(
                ks, pred_consts, spans, c, u, s, freq_table, v)
            )(cols, unit, strat, valid)
            return jax.tree.map(lambda x: x.sum(axis=0), mom)
        return jax.jit(fn)

    pspec = P(data_axes)

    def fn(ks, pred_consts, cols, unit, strat, freq_table, valid, spans=None):
        def per_shard(c, u, s, ft, v):
            mom = jax.tree.map(
                lambda x: x[0],
                jax.vmap(lambda cc, uu, ss, vv: shard_fn(
                    ks, pred_consts, spans, cc, uu, ss, ft, vv))(c, u, s, v))
            leaves, treedef = jax.tree.flatten(mom)
            # ONE collective for the whole batch: psum the stacked [7, Q, G]
            # statistics tensor instead of seven per-leaf reductions.
            merged = jax.lax.psum(jnp.stack(leaves), data_axes)
            return jax.tree.unflatten(treedef, list(merged))
        inner = jax.shard_map(per_shard, mesh=mesh,
                              in_specs=(pspec, pspec, pspec, P(), pspec),
                              out_specs=P(), check_vma=not use_pallas)
        return inner(cols, unit, strat, freq_table, valid)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Variational-subsampling scans (VerdictDB-style CIs, estimators.py §subsamp.)
# ---------------------------------------------------------------------------
#
# The CI path needs per-(group, subsample) partial moments; they come out of
# the SAME segment reduction the plain scan runs, just over n_groups·B
# segments with ids g·B + j. Subsample membership j is a pure function of
# the row's linear slot index — hashed, NOT idx % B, so membership is
# decorrelated from entry-key order (consecutive slots of a stratum share
# nearly-sorted entry keys; a modulo would give systematically balanced
# subsamples and bias the replicate spread low). These are jnp-path programs:
# subsampled scans are the CI/verification path, and fall back from Pallas.

_SUBSAMPLE_HASH_SHIFT = 7   # decouple from shard_valid_mask's low-bit use


def subsample_codes(n_shards: int, n_local: int,
                    n_subsamples: int) -> np.ndarray:
    """int32[S, n_local] deterministic subsample id per slot, hashed from the
    linear slot index (slot j ↔ shard j % S, local j // S). Stable across
    appends that keep the padded shape (a slot keeps its subsample for life),
    so subsampled programs cache exactly like the plain scans."""
    lin = (np.arange(n_local, dtype=np.uint32)[None, :] * np.uint32(n_shards)
           + np.arange(n_shards, dtype=np.uint32)[:, None])
    h = (lin * np.uint32(_SHARD_HASH_MULT)) >> np.uint32(_SUBSAMPLE_HASH_SHIFT)
    return (h % np.uint32(n_subsamples)).astype(np.int32)


def make_subsampled_query_fn(struct, value_col: str | None,
                             group_col: str | None, n_groups: int,
                             n_subsamples: int, mesh: Mesh | None = None,
                             data_axes: tuple[str, ...] = ("data",)):
    """make_query_fn analogue with per-subsample segments. Returns jitted
    fn(k, pred_vals, sub, cols, unit, strat, freq_table, valid) ->
    GroupedMoments with [n_groups·B] leaves (group-major: segment g·B + j).
    `sub` is the subsample_codes array, a traced arg like the block."""
    b = n_subsamples

    def shard_fn(k, pred_vals, sub, cols, unit, strat, ftab, valid):
        values = (cols[value_col].astype(jnp.float32)
                  if value_col is not None else jnp.ones_like(unit))
        gcodes = (cols[group_col].astype(jnp.int32)
                  if group_col is not None else jnp.zeros(unit.shape, jnp.int32))
        freq, ek = derive_ht(unit, strat, ftab)
        mask = eval_pred(struct, cols, pred_vals) & valid & (ek < k)
        rates = jnp.minimum(1.0, k / freq)
        g = gcodes * b + sub
        return est_lib.grouped_moments(values, rates, mask, g, n_groups * b)

    if mesh is None:
        def fn(k, pred_vals, sub, cols, unit, strat, freq_table, valid):
            mom = jax.vmap(lambda sb, c, u, s, v: shard_fn(
                k, pred_vals, sb, c, u, s, freq_table, v)
            )(sub, cols, unit, strat, valid)
            return jax.tree.map(lambda x: x.sum(axis=0), mom)
        return jax.jit(fn)

    pspec = P(data_axes)

    def fn(k, pred_vals, sub, cols, unit, strat, freq_table, valid):
        inner = jax.shard_map(
            lambda sb, c, u, s, ft, v: _merge_psum(
                jax.tree.map(lambda x: x[0],
                             jax.vmap(lambda sbb, cc, uu, ss, vv: shard_fn(
                                 k, pred_vals, sbb, cc, uu, ss, ft, vv)
                             )(sb, c, u, s, v)),
                data_axes),
            mesh=mesh,
            in_specs=(pspec, pspec, pspec, pspec, P(), pspec),
            out_specs=P(),
        )
        return inner(sub, cols, unit, strat, freq_table, valid)
    return jax.jit(fn)


def make_batched_subsampled_query_fn(struct, value_col: str | None,
                                     group_col: str | None, n_groups: int,
                                     n_subsamples: int,
                                     mesh: Mesh | None = None,
                                     data_axes: tuple[str, ...] = ("data",)):
    """Batched analogue: fn(ks, pred_consts, sub, cols, unit, strat,
    freq_table, valid) -> GroupedMoments [Q, n_groups·B]. One family pass
    serves Q queries' point estimates AND their subsampling CIs: relative to
    make_batched_query_fn the only extra cost is the B-times-wider segment
    reduction — the streamed bytes are identical."""
    b = n_subsamples

    def shard_fn(ks, pred_consts, sub, cols, unit, strat, ftab, valid):
        values = (cols[value_col].astype(jnp.float32)
                  if value_col is not None else jnp.ones_like(unit))
        gcodes = (cols[group_col].astype(jnp.int32)
                  if group_col is not None else jnp.zeros(unit.shape, jnp.int32))
        freq, ek = derive_ht(unit, strat, ftab)
        g = gcodes * b + sub

        def one(k, consts):
            mask = eval_pred_flat(struct, cols, consts) & valid & (ek < k)
            rates = jnp.minimum(1.0, k / freq)
            return est_lib.grouped_moments(values, rates, mask, g,
                                           n_groups * b)
        return jax.vmap(one)(ks, pred_consts)

    if mesh is None:
        def fn(ks, pred_consts, sub, cols, unit, strat, freq_table, valid):
            mom = jax.vmap(lambda sb, c, u, s, v: shard_fn(
                ks, pred_consts, sb, c, u, s, freq_table, v)
            )(sub, cols, unit, strat, valid)
            return jax.tree.map(lambda x: x.sum(axis=0), mom)
        return jax.jit(fn)

    pspec = P(data_axes)

    def fn(ks, pred_consts, sub, cols, unit, strat, freq_table, valid):
        def per_shard(sb, c, u, s, ft, v):
            mom = jax.tree.map(
                lambda x: x[0],
                jax.vmap(lambda sbb, cc, uu, ss, vv: shard_fn(
                    ks, pred_consts, sbb, cc, uu, ss, ft, vv))(sb, c, u, s, v))
            leaves, treedef = jax.tree.flatten(mom)
            merged = jax.lax.psum(jnp.stack(leaves), data_axes)
            return jax.tree.unflatten(treedef, list(merged))
        inner = jax.shard_map(per_shard, mesh=mesh,
                              in_specs=(pspec, pspec, pspec, pspec, P(),
                                        pspec),
                              out_specs=P())
        return inner(sub, cols, unit, strat, freq_table, valid)
    return jax.jit(fn)


def make_subsampled_quantile_fn(struct, value_col: str,
                                group_col: str | None, n_groups: int,
                                n_subsamples: int,
                                mesh: Mesh | None = None,
                                data_axes: tuple[str, ...] = ("data",),
                                n_bins: int = 256):
    """QUANTILE subsampling program (jnp flat layout like make_quantile_fn).

    Returns jitted fn(k, pred_vals, level, sub, cols, unit, strat,
    freq_table, valid) -> (mom_sub [G·B], qval[G], dens[G], qsub[G·B]):
    the per-subsample moments, the FULL-sample histogram quantile (point
    estimate + density, same numerics as the plain path), and per-subsample
    replicate quantiles — all from one streaming pass over the prefix."""
    b = n_subsamples

    def fn(k, pred_vals, level, sub, cols, unit, strat, freq_table, valid):
        flat = {c: v.reshape(-1) for c, v in cols.items()}
        fqf, ekf = derive_ht(unit.reshape(-1), strat.reshape(-1), freq_table)
        mask = eval_pred(struct, flat, pred_vals) & valid.reshape(-1) \
            & (ekf < k)
        rates = jnp.minimum(1.0, k / fqf)
        w = mask.astype(jnp.float32) / rates
        g = (flat[group_col].astype(jnp.int32) if group_col
             else jnp.zeros(ekf.shape, jnp.int32))
        g_sub = g * b + sub.reshape(-1)
        values = flat[value_col].astype(jnp.float32)
        mom_sub = est_lib.grouped_moments(values, rates, mask, g_sub,
                                          n_groups * b)
        qval, dens = grouped_quantile(values, w, g, n_groups, level,
                                      n_bins=n_bins)
        qsub, _ = grouped_quantile(values, w, g_sub, n_groups * b, level,
                                   n_bins=n_bins)
        return mom_sub, qval, dens, qsub
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Fault-domain sharded scans (replicated logical shards over a striped block)
# ---------------------------------------------------------------------------
#
# The striped block's physical [S_dev, n_local] layout balances LOAD; fault
# domains are a second, logical partition: each stratum hashes to one of
# `n_logical` shards, so the shards are disjoint row sets whose per-shard
# GroupedMoments partials sum exactly to the full-scan statistics. Because
# every compiled query program takes the block's `valid` mask as a TRACED
# argument, a per-shard scan is just the same compiled program called with
# `valid & (stratum_hash == s)` — no recompilation, no re-striping.
#
# This path engages only under an armed non-empty FaultPlan (engine.py's
# engagement rule): per-shard float summation order differs from the fused
# single pass, and the empty-plan bit-identity contract (docs/FAULTS.md)
# forbids that unless faults are actually possible.

_SHARD_HASH_MULT = 2654435761     # Knuth multiplicative hash (fits uint32)


@functools.partial(jax.jit, static_argnames=("n_logical",))
def shard_valid_mask(strat: jax.Array, valid: jax.Array, shard,
                     *, n_logical: int) -> jax.Array:
    """Validity mask restricted to one logical fault-domain shard: stratum
    ids hash onto [0, n_logical) so shards are disjoint stratum partitions
    (the FlameDB pattern the ROADMAP names). `shard` is traced — one
    compiled mask program serves every shard."""
    h = (strat.astype(jnp.uint32) * jnp.uint32(_SHARD_HASH_MULT)) \
        % jnp.uint32(n_logical)
    return valid & (h == jnp.uint32(shard))


def shard_of_strata(strata: np.ndarray, n_logical: int) -> np.ndarray:
    """Host-side mirror of shard_valid_mask's hash (tests / planning)."""
    h = (np.asarray(strata, dtype=np.uint32) * np.uint32(_SHARD_HASH_MULT))
    return (h % np.uint32(n_logical)).astype(np.int32)


@jax.jit
def _poison_moments(mom: est_lib.GroupedMoments) -> est_lib.GroupedMoments:
    """Corrupt a partial with NaNs (what a poison fault turns a shard's
    result into — the detection layer must refuse it)."""
    return jax.tree.map(lambda x: x * jnp.float32(jnp.nan), mom)


@dataclasses.dataclass(frozen=True)
class ShardScanReport:
    """What the sharded scan survived — the provenance an Answer carries."""
    n_shards: int                 # logical shards scanned
    lost: tuple[int, ...]         # shards with no surviving replica
    rerouted: tuple[int, ...]     # shards served by a replica > 0
    reweight: float               # HT factor S/(S-L) applied (1.0 = none)

    @property
    def degraded(self) -> bool:
        return bool(self.lost)


def merge_shard_reports(reports: Sequence["ShardScanReport | None"]
                        ) -> "ShardScanReport | None":
    """Union the reports of chunked scans over one family (engine chunks
    batches past _MAX_SCAN_BATCH): conservative provenance — a shard lost
    in ANY chunk is reported lost, the widest reweight wins."""
    reps = [r for r in reports if r is not None]
    if not reps:
        return None
    lost = sorted({s for r in reps for s in r.lost})
    rerouted = sorted({s for r in reps for s in r.rerouted})
    return ShardScanReport(max(r.n_shards for r in reps), tuple(lost),
                           tuple(rerouted),
                           max(r.reweight for r in reps))


def run_sharded_scan(call, striped: StripedFamily, *, n_logical: int,
                     n_replicas: int = 2, site_ctx: dict | None = None,
                     deadline_s: float | None = None, placement=None
                     ) -> tuple[est_lib.GroupedMoments, ShardScanReport]:
    """Execute `call(valid_mask) -> GroupedMoments` once per logical shard,
    with replica re-route and HT reweighting of survivors.

    Per shard: up to `n_replicas` attempts run the SAME deterministic scan
    under distinct (shard, replica) fault-site identities — a replica is a
    re-execution that a fault plan can fail independently, exactly like a
    second physical copy. An attempt fails on an injected kill, a partial
    that is not finite (poison detection), or — when `deadline_s` is set —
    an attempt exceeding the straggler deadline (StragglerPolicy's
    deadline = factor × median, precomputed by the caller). Shards whose
    every replica fails are LOST: the surviving partials are summed and
    HT-reweighted by S/(S-L) (estimators.reweight_moments), which widens
    every CI. Raises AllShardsLostError when nothing survives.

    With a `FamilyPlacement` (sharding/placement.py) each replica attempt
    additionally carries the PROCESS it executes on: the chain length
    overrides `n_replicas` (hot families run longer chains) and the fault
    site gains a `process` key, so one FaultSpec matching
    `(("process", p),)` kills every attempt homed on process p — machine
    loss, with fail-over to replicas placed elsewhere. Specs matching only
    shard/replica keys behave exactly as before (extra ctx keys are ignored
    by FaultSpec.matches), so PR-6 plans and tests are untouched.
    """
    ctx = dict(site_ctx or {})
    partials: list[est_lib.GroupedMoments] = []
    lost: list[int] = []
    rerouted: list[int] = []
    for s in range(n_logical):
        mask = shard_valid_mask(striped.strat, striped.valid, s,
                                n_logical=n_logical)
        chain = (placement.replicas_for(s) if placement is not None
                 else tuple(None for _ in range(n_replicas)))
        mom = None
        for r, proc in enumerate(chain):
            t0 = time.perf_counter()
            pctx = {} if proc is None else {"process": proc}
            # Each attempt is its own span: a trace of a degraded query
            # shows every replica tried, which ones a fault plan failed
            # (attrs carry ok=False + error), which process each attempt
            # was placed on, and which one finally served.
            with obs_trace.span("scan.shard", shard=s, replica=r,
                                **pctx) as sp:
                try:
                    action = inject.site("shard.scan", shard=s, replica=r,
                                         **pctx, **ctx)
                    m = call(mask)
                    if action == "poison":
                        m = jax.tree.map(lambda x: x.block_until_ready(),
                                         _poison_moments(m))
                    if deadline_s is not None \
                            and time.perf_counter() - t0 > deadline_s:
                        raise ShardScanError(
                            f"shard {s} replica {r} missed the straggler "
                            f"deadline ({deadline_s:.3f}s)")
                    if not est_lib.moments_finite(m):
                        raise ShardScanError(
                            f"shard {s} replica {r} returned non-finite "
                            "statistics (poisoned partial)")
                    mom = m
                    sp.set(ok=True)
                    break
                except FaultError as e:
                    sp.set(ok=False, error=type(e).__name__)
                    continue    # next replica; non-fault errors propagate
        if mom is None:
            lost.append(s)
        else:
            if r > 0:
                rerouted.append(s)
            partials.append(mom)
    if not partials:
        n_rep = (placement.n_replicas if placement is not None
                 else n_replicas)
        raise AllShardsLostError(
            f"all {n_logical} logical shards lost every one of "
            f"{n_rep} replicas")
    total = jax.tree.map(lambda *xs: functools.reduce(jnp.add, xs), *partials)
    factor = n_logical / (n_logical - len(lost))
    if lost:
        total = est_lib.reweight_moments(total, factor)
    report = ShardScanReport(n_logical, tuple(lost), tuple(rerouted), factor)
    return total, report


# ---------------------------------------------------------------------------
# Grouped weighted quantiles (histogram method, Table 2 variance)
# ---------------------------------------------------------------------------

def hist_to_quantile(hist: jax.Array, lo, hi, q):
    """(quantile_value[G], density[G]) from per-group histograms over the
    fixed range [lo, hi]. hist is f32[G, n_bins] — the transpose of the
    fused quantile kernel's output, or grouped_quantile's own histogram.

    Groups with ZERO selected mass (no row passed the predicate/prefix)
    return a well-defined (0, 0) instead of the NaN/garbage the clamped
    total division used to produce."""
    n_bins = hist.shape[1]
    lo = jnp.asarray(lo, jnp.float32)
    span = jnp.maximum(jnp.asarray(hi, jnp.float32) - lo, 1e-12)
    cum = jnp.cumsum(hist, axis=1)
    mass = cum[:, -1]
    total = jnp.maximum(cum[:, -1:], 1e-12)
    cdf = cum / total
    # first bin where cdf >= q
    idx = jnp.argmax(cdf >= q, axis=1)
    bin_w = span / n_bins
    left_edge = lo + idx * bin_w
    prev_cdf = jnp.where(idx > 0, jnp.take_along_axis(cdf, jnp.maximum(idx - 1, 0)[:, None], 1)[:, 0], 0.0)
    bin_mass = jnp.take_along_axis(cdf, idx[:, None], 1)[:, 0] - prev_cdf
    frac = jnp.where(bin_mass > 1e-12, (q - prev_cdf) / jnp.maximum(bin_mass, 1e-12), 0.5)
    qval = left_edge + frac * bin_w
    density = jnp.take_along_axis(hist, idx[:, None], 1)[:, 0] / (total[:, 0] * bin_w)
    empty = mass <= 0.0
    return jnp.where(empty, 0.0, qval), jnp.where(empty, 0.0, density)


def grouped_quantile(values: jax.Array, weights: jax.Array, gcodes: jax.Array,
                     n_groups: int, q: float, n_bins: int = 256,
                     lo: float | None = None, hi: float | None = None):
    """Weighted per-group quantile via a fixed-bin histogram + interpolation.
    Returns (quantile_value[G], density_at_quantile[G]) for Table-2 variance."""
    v = values.astype(jnp.float32)
    lo_ = jnp.asarray(lo if lo is not None else jnp.min(jnp.where(weights > 0, v, jnp.inf)))
    hi_ = jnp.asarray(hi if hi is not None else jnp.max(jnp.where(weights > 0, v, -jnp.inf)))
    # Empty selection: the masked min/max above are ±inf, which would turn
    # every bin index into NaN. Force a degenerate-but-finite range;
    # hist_to_quantile then reports (0, 0) for the all-empty groups.
    lo_ = jnp.where(jnp.isfinite(lo_), lo_, 0.0)
    hi_ = jnp.where(jnp.isfinite(hi_), hi_, 0.0)
    span = jnp.maximum(hi_ - lo_, 1e-12)
    bins = jnp.clip(((v - lo_) / span * n_bins).astype(jnp.int32), 0, n_bins - 1)
    flat = gcodes.astype(jnp.int32) * n_bins + bins
    hist = jax.ops.segment_sum(weights, flat, num_segments=n_groups * n_bins)
    return hist_to_quantile(hist.reshape(n_groups, n_bins), lo_, hi_, q)


def make_quantile_fn(struct, value_col: str, group_col: str | None,
                     n_groups: int, mesh: Mesh | None = None,
                     data_axes: tuple[str, ...] = ("data",),
                     use_pallas: bool = False,
                     n_bins: int = 256):
    """ONE-PASS quantile program over a STRIPED block.

    Returns jitted fn(k, pred_vals, level, lo, hi, cols, unit, strat,
    freq_table, valid, spans=None) -> (GroupedMoments, quantile_value[G],
    density[G]):
    the grouped sufficient statistics AND the histogram quantile come out of
    a single streaming pass, so a QUANTILE answer no longer pays a second
    full-column read after the moments scan.

    The pallas path runs the fused quantile kernel (moments + bins×groups
    histogram in one VMEM-resident pass) over the family-global [lo, hi]
    range the engine caches per (family, value column), its row grid
    bounded by `spans` (row_spans). The jnp path keeps
    the original data-dependent range (lo/hi args unused) so its histogram
    numerics are unchanged from the pre-fusion pass; histogram results are
    order-invariant over the padded striped layout (padding/ghosts carry
    zero weight). Both inherit the striped shape class, so appends that fit
    existing padding reuse the compiled program."""
    atoms = flat_atoms(struct)
    ops_struct = tuple(tuple(op for _, op in conj) for conj in struct)
    use_pallas = on_kernel(struct, use_pallas, quantile=True, mesh=mesh)
    acol_names, atom_slots = dedup_atom_slots(atoms)

    if use_pallas:
        def fn(k, pred_vals, level, lo, hi, cols, unit, strat, freq_table,
               valid, spans=None):
            from repro.kernels import ops as kops
            consts = (jnp.stack(list(flatten_pred_vals(pred_vals)))
                      if atoms else jnp.zeros((0,), jnp.float32))

            def shard(c, u, s, v):
                values = c[value_col].astype(jnp.float32)
                gcodes = (c[group_col].astype(jnp.int32) if group_col
                          else jnp.zeros(u.shape, jnp.int32))
                acols = tuple(c[a] for a in acol_names)
                return kops.quantile_scan(values, u, s, freq_table, v,
                                          acols, gcodes, k, lo, hi, consts,
                                          ops_struct, atom_slots, n_groups,
                                          n_bins, spans)
            mom, hist = jax.vmap(shard)(cols, unit, strat, valid)
            mom = jax.tree.map(lambda x: x.sum(axis=0), mom)
            qval, dens = hist_to_quantile(hist.sum(axis=0).T, lo, hi, level)
            return mom, qval, dens
        return jax.jit(fn)

    def fn(k, pred_vals, level, lo, hi, cols, unit, strat, freq_table, valid,
           spans=None):
        flat = {c: v.reshape(-1) for c, v in cols.items()}
        fqf, ekf = derive_ht(unit.reshape(-1), strat.reshape(-1), freq_table)
        mask = eval_pred(struct, flat, pred_vals) & valid.reshape(-1) \
            & (ekf < k)
        rates = jnp.minimum(1.0, k / fqf)
        w = mask.astype(jnp.float32) / rates
        g = (flat[group_col].astype(jnp.int32) if group_col
             else jnp.zeros(ekf.shape, jnp.int32))
        values = flat[value_col].astype(jnp.float32)
        mom = est_lib.grouped_moments(values, rates, mask, g, n_groups)
        qval, dens = grouped_quantile(values, w, g, n_groups, level)
        return mom, qval, dens
    return jax.jit(fn)
