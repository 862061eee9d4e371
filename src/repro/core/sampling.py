"""Multi-dimensional, multi-resolution stratified sample families (paper §3.1).

TPU-native adaptation (DESIGN.md §2):

A family SFam(φ) is materialized as ONE compacted table whose rows are sorted
by `entry_key = u * F(x)` where `u ~ U[0,1)` is a per-row random priority and
`F(x)` the row's stratum frequency on φ. Membership in S(φ, K) is exactly
`entry_key < K` (u < min(1, K/F)), so:

  * resolutions are nested (paper Fig 3/4) by construction,
  * S(φ, K) is a *prefix* of the materialized family — a smaller resolution
    scans strictly fewer bytes (the TPU analogue of Fig 4's HDFS block
    nesting), and
  * the per-row inclusion probability rate(row, K) = min(1, K/F) is exact,
    giving unbiased Horvitz–Thompson estimates (§4.3).

This is Poisson (expected-K) stratification: E[|stratum ∩ S|] = min(F, K).
The paper's exact-K variant is provided as `stratified_exact_k` (host
reference) — see DESIGN.md "assumption changes" for why Poisson is the
distributed-TPU-native choice.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import table as table_lib
from repro.core.types import ColumnKind


class _LazyFamilyColumns(table_lib._LazyColumns):
    """Family-level lazy mirror (shares the refresh semantics with the
    table-level one — table._LazyColumns).

    The serving path reads only the STRIPED block (built host-side), so a
    family produced by `merge_family`/`apply_tombstones`/`_assemble_family`
    never needs its own device arrays unless someone asks — deferring them
    cuts per-mutation host→device traffic to the striped scatters alone
    (ROADMAP lazy-mirror item). Keys are always present (membership,
    iteration, and deletion are host-only); only values upload lazily.
    """

    def __init__(self, mapping, owner: "SampleFamily", stale=()):
        super().__init__(mapping)
        self._owner = owner
        self._stale = set(stale)

    def _stale_keys(self) -> set:
        return self._stale

    def _host(self, key):
        return self._owner.columns_host[key]

    def __delitem__(self, key):
        self._stale.discard(key)
        super().__delitem__(key)

    @property
    def resident(self) -> frozenset[str]:
        """Column names whose device buffers exist (materialized)."""
        return frozenset(k for k in super().keys() if k not in self._stale)

    def clone_for(self, owner: "SampleFamily") -> "_LazyFamilyColumns":
        out = _LazyFamilyColumns({}, owner, self._stale)
        for k in super().keys():
            dict.__setitem__(out, k, dict.__getitem__(self, k))
        return out


# Device-mirror fields that materialize lazily from host state when a family
# is constructed with them set to None (see SampleFamily.__getattribute__).
_LAZY_DEVICE_FIELDS = ("columns", "freq", "entry_key", "unit")


@dataclasses.dataclass
class SampleFamily:
    """Materialized SFam(φ): the largest sample + metadata for all resolutions.

    The device-mirror fields (`columns`, `freq`, `entry_key`, `unit`) may be
    constructed as None when the corresponding host mirrors are present: they
    then materialize lazily on first attribute access. Queries read only the
    striped executor block, so the incremental merge/tombstone paths never
    pay the upload (`device_resident()` reports what has materialized).
    """
    phi: tuple[str, ...]              # stratification columns (sorted)
    ks: tuple[float, ...]             # resolutions, descending: K_1 > K_1/c > ...
    # sampled rows, sorted by entry_key (None ⇒ lazy from columns_host)
    columns: dict[str, jax.Array] | None
    freq: jax.Array | None            # f32[n] stratum frequency F(x) per row
    entry_key: jax.Array | None       # f32[n] = u * F(x), ascending
    prefix_sizes: tuple[int, ...]     # |S(φ, K_i)| for each K_i (row counts)
    n_rows: int                       # rows materialized (= prefix_sizes[0])
    table_rows: int                   # LIVE rows in the original table
    n_distinct: int                   # |D(φ)|
    # INCLUSION frequency per distinct value: the F the entry keys and HT
    # rates are computed under. Under mutation this is the CUMULATIVE
    # (ever-inserted, i.e. physical) histogram — monotone non-decreasing, so
    # re-keying u·F only ever pushes rows OUT of the K₁ prefix and a row's
    # inclusion probability min(1, K/F) stays exact no matter what was
    # deleted around it (docs/MAINTENANCE.md tombstone protocol). For
    # append-only families it equals the live histogram, as before.
    stratum_freqs: np.ndarray
    # Incremental-maintenance state (docs/MAINTENANCE.md). `unit` is the raw
    # per-row priority u — kept so a merge can recompute entry_key = u·F_new
    # bit-identically to a from-scratch rebuild with the same units.
    unit: jax.Array | None = None          # f32[n] per-row u ~ U[0,1)
    strata_keys: np.ndarray | None = None  # [D, |φ|] per-stratum column codes
    row_strata: np.ndarray | None = None   # int64[n] stable stratum id per row
    entry_key_host: np.ndarray | None = None  # host mirror (hot-path prefixes)
    # Host mirrors of the merge inputs: without them every append epoch would
    # read the whole sample back device→host — O(sample), not O(delta).
    columns_host: dict[str, np.ndarray] | None = None
    unit_host: np.ndarray | None = None
    # Mutation state: physical base-table row index per sampled row (the
    # stable id tombstones are matched on), and LIVE per-stratum counts
    # (drift/stats; decremented by tombstones while stratum_freqs is not).
    row_ids: np.ndarray | None = None      # int64[n]
    stratum_live: np.ndarray | None = None # int64[D]; None ⇒ == stratum_freqs

    def __getattribute__(self, name):
        # Deliberate tradeoff: intercepting every attribute read costs one
        # extra Python call + tuple test on hot-path reads (fam.ks etc.) —
        # negligible next to the ms-scale scans those paths drive — in
        # exchange for full transparency: no constructor or consumer
        # changes, legacy eager families keep working. Generic all-field
        # readers (repr, asdict, debuggers) DO materialize the mirrors;
        # use lazy_replace/device_resident where that matters.
        if name in _LAZY_DEVICE_FIELDS:
            val = object.__getattribute__(self, name)
            if val is None:
                val = object.__getattribute__(self, "_materialize")(name)
            return val
        return object.__getattribute__(self, name)

    def _materialize(self, name):
        """Build one device mirror from host state; returns None when the
        host source is absent (legacy pre-incremental families keep their
        `unit=None` semantics)."""
        raw = object.__getattribute__
        if name == "columns":
            hosts = raw(self, "columns_host")
            if hosts is None:
                return None
            val = _LazyFamilyColumns({k: None for k in hosts}, self,
                                     stale=hosts)
        elif name == "freq":
            strata = raw(self, "row_strata")
            if strata is None:
                return None
            val = jnp.asarray(self.stratum_freqs.astype(np.float32)[strata])
        elif name == "entry_key":
            ek = raw(self, "entry_key_host")
            if ek is None:
                return None
            val = jnp.asarray(ek)
        else:  # unit
            uh = raw(self, "unit_host")
            if uh is None:
                return None
            val = jnp.asarray(uh)
        setattr(self, name, val)
        return val

    def device_resident(self) -> frozenset[str]:
        """Names of device mirrors that have actually materialized — empty
        right after an incremental merge/tombstone pass (the laziness the
        ROADMAP item asks for; tests assert on this)."""
        raw = object.__getattribute__
        out = set()
        for name in ("freq", "entry_key", "unit"):
            if raw(self, name) is not None:
                out.add(name)
        cols = raw(self, "columns")
        if isinstance(cols, _LazyFamilyColumns):
            out |= {f"columns.{c}" for c in cols.resident}
        elif cols is not None:
            out |= {f"columns.{c}" for c in cols}
        return frozenset(out)

    def lazy_replace(self, **changes) -> "SampleFamily":
        """dataclasses.replace without touching (= materializing) the lazy
        device mirrors; un-materialized fields stay un-materialized on the
        copy."""
        raw = object.__getattribute__
        kw = {f.name: raw(self, f.name) for f in dataclasses.fields(self)}
        kw.update(changes)
        cols = kw["columns"]
        out = SampleFamily(**kw)
        if isinstance(cols, _LazyFamilyColumns):
            out.columns = cols.clone_for(out)
        return out

    def host_column(self, name: str) -> np.ndarray:
        if self.columns_host is not None and name in self.columns_host:
            return self.columns_host[name]
        return np.asarray(self.columns[name])

    @property
    def k1(self) -> float:
        return self.ks[0]

    @property
    def live_freqs(self) -> np.ndarray:
        """LIVE per-stratum counts (what drift/optimizer stats should see);
        equals the inclusion freqs until a tombstone decrements it."""
        return (self.stratum_live if self.stratum_live is not None
                else self.stratum_freqs)

    def prefix_for_k(self, k: float) -> int:
        """Rows to scan for resolution cap k. Searches the HOST mirror of
        entry_key — this runs on the hot path of every query()/query_batch()
        answer, and a per-call device→host transfer of the whole key column
        would dwarf the scan it accounts for."""
        ek = self.entry_key_host
        if ek is None:
            ek = np.asarray(self.entry_key)
            self.entry_key_host = ek
        return count_below(ek, k)

    def rate(self, k: float) -> jax.Array:
        """Per-row inclusion probability at resolution k (HT weights = 1/rate)."""
        return jnp.minimum(1.0, k / self.freq)

    def storage_bytes(self, row_bytes: int) -> int:
        # +8: the f32 freq and entry_key bookkeeping columns.
        return self.n_rows * (row_bytes + 8)


@dataclasses.dataclass
class DeltaBlock:
    """The rows a merge ADDED to a family, in delta order, plus the updated
    per-stratum frequency table — exactly the payload the executor's
    incremental restripe ships to the device (one small device_put)."""
    columns: dict[str, np.ndarray]    # host, encoded; kept delta rows only
    unit: np.ndarray                  # f32[d_kept]
    strata: np.ndarray                # int32[d_kept] stable stratum ids
    freq: np.ndarray                  # f32[d_kept] F_new per row
    entry_key: np.ndarray             # f32[d_kept] = unit · F_new
    freq_table: np.ndarray            # f32[D_new] updated per-stratum F
    n_dropped_old: int                # old rows pushed past K_1 by the rescale
    row_ids: np.ndarray | None = None # int64[d_kept] physical base-row ids

    @property
    def n_rows(self) -> int:
        return int(self.unit.size)


def count_below(ek: np.ndarray, k: float) -> int:
    """Entries of the sorted key array `ek` strictly below `k`, compared in
    float64 — the same integer as `np.searchsorted(ek.astype(np.float64), k)`.

    The search runs in ek's own dtype: a float64 key against a float32 array
    makes NumPy copy the whole array to float64 on every call, which on a
    10M-row family costs more than the scan the count is for. For x in ek's
    dtype, x < k exactly when x < k rounded UP to that dtype, so the key is
    rounded to nearest and stepped up one ulp if that fell below k. A k above
    the dtype's range rounds to inf, which still counts every finite entry."""
    with np.errstate(over="ignore"):
        key = ek.dtype.type(k)
    if float(key) < k:
        key = np.nextafter(key, ek.dtype.type(np.inf))
    return int(np.searchsorted(ek, key, side="left"))


def resolution_caps(k1: float, c: float, m: int) -> tuple[float, ...]:
    """K_i = K_1 / c^i, i in [0, m) (paper §3.1)."""
    return tuple(k1 / (c ** i) for i in range(m))


def expected_sample_rows(stratum_freqs: np.ndarray, k: float) -> float:
    """E[|S(φ,K)|] = Σ_x min(F(x), K) — exact for Poisson stratification."""
    return float(np.minimum(stratum_freqs, k).sum())


def base_units(n: int, seed: int, *, uniform: bool = False) -> np.ndarray:
    """Per-row random priorities u ~ U[1e-7, 1) for a table's initial rows.
    The uniform family salts the seed so R(p) and SFam(φ) draw independently
    (matches the original build_family / build_uniform_family streams)."""
    key = jax.random.PRNGKey((seed ^ 0x5EED) if uniform else seed)
    # np.array copies: np.asarray of a device array is a read-only view,
    # and callers such as the mutation oracle write into the units.
    return np.array(jax.random.uniform(key, (n,), dtype=jnp.float32,
                                       minval=1e-7, maxval=1.0))


def delta_units(n: int, seed: int, epoch: int, *,
                uniform: bool = False) -> np.ndarray:
    """Per-row priorities for the rows of append epoch `epoch` (1-based).
    Deterministic in (seed, epoch), independent across epochs — so a
    from-scratch rebuild fed base_units ++ delta_units(…,1) ++ … is a
    bit-exact oracle for the incremental merge path. Host-side numpy RNG:
    the ingest hot path must not pay a device-program compile per delta
    shape (base_units stays on the jax stream for seed compatibility)."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFFFFFFFFFF, epoch, 1 if uniform else 0]))
    return np.maximum(rng.random(n, dtype=np.float32), np.float32(1e-7))


def decay_units(n: int, seed: int, epoch: int) -> np.ndarray:
    """Per-row priorities for inclusion-frequency decay epoch `epoch`
    (1-based): one full-table-length draw, indexed by PHYSICAL row id, from
    which a decay pass reads only the rows of the strata it resets.
    Deterministic in (seed, epoch) and salted away from the append streams —
    so the from-scratch oracle can reproduce any decay by redrawing the same
    stream (host numpy RNG, like delta_units: no device compile on the
    maintenance path)."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFFFFFFFFFF, epoch, 2]))
    return np.maximum(rng.random(n, dtype=np.float32), np.float32(1e-7))


def _assemble_family(phi: tuple[str, ...], ks: tuple[float, ...],
                     host_cols: Mapping[str, np.ndarray], units: np.ndarray,
                     codes: np.ndarray, freqs: np.ndarray,
                     key_matrix: np.ndarray, table_rows: int, *,
                     live: np.ndarray | None = None,
                     incl_freqs: np.ndarray | None = None) -> SampleFamily:
    """Materialize a family from per-row (unit, stratum) assignments: keep
    entry_key = u·F < K_1 (live rows only), sort ascending, cut prefixes.
    Shared by the from-scratch builders and (via identical float math) the
    merge/mutation oracle. `freqs` are the LIVE per-stratum counts;
    `incl_freqs` (default: freqs) are the inclusion frequencies keys/rates
    use — the mutation oracle passes the cumulative physical histogram."""
    k1 = ks[0]
    if incl_freqs is None:
        incl_freqs = freqs
    row_freq = incl_freqs.astype(np.float32)[codes] if len(codes) \
        else np.zeros(0, np.float32)
    entry_key = units.astype(np.float32) * row_freq
    keep = entry_key < k1
    if live is not None:
        keep &= live
    order = np.argsort(entry_key[keep], kind="stable")
    idx = np.nonzero(keep)[0][order]
    ek = entry_key[idx]
    prefixes = tuple(count_below(ek, k) for k in ks)
    cols_host = {name: np.asarray(arr)[idx] for name, arr in host_cols.items()}
    unit_host = units.astype(np.float32)[idx]
    return SampleFamily(
        phi=phi, ks=ks,
        columns=None, freq=None, entry_key=None,   # lazy device mirrors
        prefix_sizes=prefixes, n_rows=int(idx.size), table_rows=table_rows,
        n_distinct=len(incl_freqs), stratum_freqs=incl_freqs,
        unit=None,
        strata_keys=key_matrix, row_strata=codes[idx],
        entry_key_host=ek, columns_host=cols_host, unit_host=unit_host,
        row_ids=idx.astype(np.int64), stratum_live=freqs)


def build_family(tbl: table_lib.Table, phi: Sequence[str], k1: float,
                 c: float = 2.0, m: int | None = None, *,
                 seed: int = 0, units: np.ndarray | None = None,
                 cumulative_inclusion: bool = False,
                 incl_freqs: np.ndarray | None = None) -> SampleFamily:
    """Construct SFam(φ) from a table (offline sample creation, §2.2.1).

    `units` overrides the seeded per-row priorities — the host ORACLE for the
    incremental merge path: rebuilding with the concatenated unit segments of
    every append must reproduce the merged family exactly.

    On a table with tombstones only LIVE rows are sampled. A fresh build
    keys them under the live frequencies (best sample utilization);
    `cumulative_inclusion=True` keys under the cumulative PHYSICAL histogram
    instead — the oracle for the incremental mutation path, where inclusion
    frequencies count every row ever inserted and never decrement.
    `incl_freqs` overrides the inclusion histogram outright (aligned to
    combined_codes' stratum numbering) — the oracle for the DECAY path,
    where some strata's inclusion counts were reset to live counts and the
    cumulative histogram no longer describes them.
    """
    phi = tuple(sorted(phi))
    for col in phi:
        if tbl.schema.column(col).kind is not ColumnKind.CATEGORICAL:
            raise ValueError(f"stratification column {col!r} must be categorical")
    codes, key_matrix = table_lib.combined_codes(tbl, phi)
    n_distinct = int(codes.max()) + 1 if len(codes) else 0
    live = tbl.live
    live_freqs = table_lib.stratum_frequencies(
        codes if live is None else codes[live], n_distinct)
    if incl_freqs is not None:
        incl = np.asarray(incl_freqs, dtype=np.int64)
    else:
        incl = (table_lib.stratum_frequencies(codes, n_distinct)
                if cumulative_inclusion else None)

    if m is None:
        m = max(1, int(math.floor(math.log(max(k1, 2.0), c))))
    ks = resolution_caps(k1, c, m)
    if units is None:
        units = base_units(tbl.n_rows, seed)
    host_cols = {c: tbl.host_column(c) for c in tbl.columns}
    return _assemble_family(phi, ks, host_cols, units, codes, live_freqs,
                            key_matrix[:n_distinct], tbl.n_live,
                            live=live, incl_freqs=incl)


def build_uniform_family(tbl: table_lib.Table, fraction: float, c: float = 2.0,
                         m: int | None = None, *, seed: int = 0,
                         units: np.ndarray | None = None, k1: float | None = None,
                         cumulative_inclusion: bool = False) -> SampleFamily:
    """Uniform family R(p): stratification on φ=∅ — one stratum of size N
    (live rows), K_1 = p·N. rate = K/N = sampling fraction; entry_key = u·N.
    `k1` overrides p·N exactly (the mutation oracle needs the incremental
    family's cap bit-for-bit, not a fraction round-trip)."""
    n = tbl.n_rows
    n_live = tbl.n_live
    if k1 is None:
        k1 = fraction * n_live
    if m is None:
        m = max(1, int(math.floor(math.log(max(k1, 2.0), c))))
    ks = resolution_caps(k1, c, m)
    if units is None:
        units = base_units(n, seed, uniform=True)
    host_cols = {c: tbl.host_column(c) for c in tbl.columns}
    return _assemble_family((), ks, host_cols, units,
                            np.zeros(n, dtype=np.int64),
                            np.array([n_live], dtype=np.int64),
                            np.zeros((1, 0), dtype=np.int32), n_live,
                            live=tbl.live,
                            incl_freqs=(np.array([n], dtype=np.int64)
                                        if cumulative_inclusion else None))


def merge_family(fam: SampleFamily, delta_columns: Mapping[str, np.ndarray],
                 units: np.ndarray, *, new_k1: float | None = None,
                 c: float = 2.0,
                 start_row: int | None = None) -> tuple[SampleFamily, DeltaBlock]:
    """Merge an append-only delta into a materialized family (§3.2.3/§4.5).

    Incremental counterpart of build_family: the delta's rows are keyed with
    the SAME entry_key = u·F(x) scheme under the UPDATED per-stratum
    frequencies, and existing rows are re-keyed u·F_new from their stored
    unit — so Horvitz–Thompson rates min(1, K/F_new) stay exact and the
    nested-prefix invariant is preserved by construction. Because appends
    only grow F, re-keying only ever pushes rows OUT of the K_1 prefix,
    never in: no access to unsampled base rows is needed. The result is
    bit-identical to `build_family(appended_table, units=all_units)`.

    `new_k1` resizes the largest cap (the uniform family keeps K_1 = p·N as
    N grows); stratified families keep their configured cap (pass None).
    Raises KeyError if the family carries columns the delta lacks (e.g.
    gathered join attributes — the engine strips those before merging).
    """
    phi = fam.phi
    missing = [name for name in fam.columns if name not in delta_columns]
    if missing:
        raise KeyError(
            f"delta lacks columns {missing} present on family {phi!r} — "
            "strip gathered join columns before merging")
    live_old = fam.live_freqs
    if start_row is None:
        # Fallback: the inclusion-frequency total counts every physical row
        # the family has tracked since build. Only exact when the family's
        # inclusion freqs are cumulative from physical row 0 (true unless it
        # was freshly built on an already-tombstoned table — the engine
        # passes the table's authoritative delta.start_row).
        start_row = int(fam.stratum_freqs.sum())
    if phi:
        mat = np.stack([np.asarray(delta_columns[col], dtype=np.int32)
                        for col in phi], axis=1)
        dcodes, key_matrix = table_lib.map_codes_stable(mat, fam.strata_keys)
        new_freqs = table_lib.extend_frequencies(fam.stratum_freqs, dcodes,
                                                 len(key_matrix))
        new_live = table_lib.extend_frequencies(live_old, dcodes,
                                                len(key_matrix))
        ks = fam.ks
    else:
        d = len(next(iter(delta_columns.values())))
        dcodes = np.zeros(d, dtype=np.int64)
        key_matrix = fam.strata_keys
        # Extend the family's OWN inclusion base (exactly like the stratified
        # branch extends fam.stratum_freqs) — not the table's physical count:
        # a family freshly built on an already-tombstoned table has a live
        # inclusion base, and keying against the physical count while the
        # caller scales K₁ from the live base would silently shrink rates.
        new_freqs = np.array([int(fam.stratum_freqs[0]) + d], dtype=np.int64)
        new_live = np.array([int(live_old[0]) + d], dtype=np.int64)
        ks = (resolution_caps(new_k1, c, len(fam.ks))
              if new_k1 is not None else fam.ks)
    k1 = ks[0]
    freqs_f32 = new_freqs.astype(np.float32)

    # Re-key existing sample rows under the grown frequencies (host
    # mirrors: no device read-back on the ingest path).
    old_units = (fam.unit_host if fam.unit_host is not None
                 else np.asarray(fam.unit))
    old_strata = fam.row_strata
    old_freq = freqs_f32[old_strata]
    old_ek = old_units * old_freq
    keep_old = old_ek < k1

    # Key and filter the delta's rows.
    units = np.asarray(units, dtype=np.float32)
    d_freq = freqs_f32[dcodes]
    d_ek = units * d_freq
    keep_d = d_ek < k1

    d_row_ids = start_row + np.arange(len(dcodes), dtype=np.int64)
    block = DeltaBlock(
        columns={name: np.asarray(delta_columns[name])[keep_d]
                 for name in fam.columns},
        unit=units[keep_d], strata=dcodes[keep_d].astype(np.int32),
        freq=d_freq[keep_d], entry_key=d_ek[keep_d],
        freq_table=freqs_f32, n_dropped_old=int((~keep_old).sum()),
        row_ids=d_row_ids[keep_d])

    ek_m = np.concatenate([old_ek[keep_old], block.entry_key])
    order = np.argsort(ek_m, kind="stable")
    ek_sorted = ek_m[order]
    prefixes = tuple(count_below(ek_sorted, k) for k in ks)

    def merge_col(old_arr, new_arr):
        old_h = np.asarray(old_arr)[keep_old]
        return np.concatenate([old_h, np.asarray(new_arr,
                                                 dtype=old_h.dtype)])[order]

    cols_host = {name: merge_col(fam.host_column(name), block.columns[name])
                 for name in fam.columns}
    unit_host = merge_col(old_units, block.unit)
    old_row_ids = (fam.row_ids if fam.row_ids is not None
                   else np.full(len(old_units), -1, dtype=np.int64))
    merged = SampleFamily(
        phi=phi, ks=ks,
        columns=None, freq=None, entry_key=None, unit=None,  # lazy mirrors
        prefix_sizes=prefixes, n_rows=int(ek_sorted.size),
        table_rows=fam.table_rows + len(dcodes),
        n_distinct=len(new_freqs), stratum_freqs=new_freqs,
        strata_keys=key_matrix,
        row_strata=merge_col(old_strata, block.strata.astype(np.int64)),
        entry_key_host=ek_sorted, columns_host=cols_host,
        unit_host=unit_host,
        row_ids=merge_col(old_row_ids, block.row_ids),
        stratum_live=new_live)
    return merged, block


@dataclasses.dataclass
class TombstoneBlock:
    """What one apply_tombstones pass removed from a family — exactly the
    payload the executor's `stripe_tombstone` ships to the device (a bitmask
    scatter over the dead sampled rows' slots; nothing else changes)."""
    row_ids: np.ndarray            # int64: dead rows that WERE in the sample
    n_tombstoned: int              # total dead rows (sampled or not)

    @property
    def n_sampled(self) -> int:
        return int(self.row_ids.size)


def apply_tombstones(fam: SampleFamily, row_ids: np.ndarray,
                     row_columns: Mapping[str, np.ndarray]
                     ) -> tuple[SampleFamily, TombstoneBlock]:
    """Apply a TableMutation's tombstones to a materialized family.

    Dead rows that were sampled are dropped from the host family (their
    striped-block slots become self-excluding ghosts via stripe_tombstone);
    per-stratum LIVE counts are decremented for every dead row, sampled or
    not. The INCLUSION frequencies — and with them every surviving row's
    entry_key and HT rate — are untouched: a row's inclusion probability
    min(1, K/F) was fixed by the frequencies it was keyed under, and deleting
    its neighbours does not change it, so estimates over the live population
    stay exactly unbiased without re-keying anything (docs/MAINTENANCE.md).

    `row_ids` are the tombstoned physical row indices; `row_columns` their
    encoded host columns as of death (TableMutation.tombstoned_columns) —
    used to locate each dead row's stratum without re-reading the base table.
    """
    row_ids = np.asarray(row_ids, dtype=np.int64)
    n_dead = int(row_ids.size)
    live_old = fam.live_freqs
    if fam.phi:
        mat = np.stack([np.asarray(row_columns[col], dtype=np.int32)
                        for col in fam.phi], axis=1)
        dcodes, keys = table_lib.map_codes_stable(mat, fam.strata_keys)
        if len(keys) != len(fam.strata_keys):
            raise ValueError(
                "tombstoned rows reference strata this family has never "
                "seen — the mutation does not belong to its table")
    else:
        dcodes = np.zeros(n_dead, dtype=np.int64)
    dec = np.bincount(dcodes, minlength=len(live_old)).astype(np.int64)
    new_live = live_old - dec
    if (new_live < 0).any():
        raise ValueError("tombstones exceed live stratum counts — rows "
                         "deleted twice?")

    if fam.row_ids is None:
        raise ValueError("family has no row_ids — built before mutation "
                         "support; rebuild it to enable deletes")
    dead = np.isin(fam.row_ids, row_ids)
    block = TombstoneBlock(row_ids=fam.row_ids[dead], n_tombstoned=n_dead)
    table_rows = fam.table_rows - n_dead
    if not dead.any():
        # lazy_replace, not dataclasses.replace: replace() reads every field
        # and would materialize the device mirrors this path never needs.
        out = fam.lazy_replace(stratum_live=new_live, table_rows=table_rows)
        return out, block

    keep = ~dead
    ek = fam.entry_key_host[keep]         # keys unchanged ⇒ still sorted
    cols_host = {name: fam.host_column(name)[keep] for name in fam.columns}
    unit_host = (fam.unit_host if fam.unit_host is not None
                 else np.asarray(fam.unit))[keep]
    row_strata = fam.row_strata[keep]
    prefixes = tuple(count_below(ek, k) for k in fam.ks)
    out = SampleFamily(
        phi=fam.phi, ks=fam.ks,
        columns=None, freq=None, entry_key=None, unit=None,  # lazy mirrors
        prefix_sizes=prefixes, n_rows=int(ek.size), table_rows=table_rows,
        n_distinct=fam.n_distinct, stratum_freqs=fam.stratum_freqs,
        strata_keys=fam.strata_keys, row_strata=row_strata,
        entry_key_host=ek, columns_host=cols_host, unit_host=unit_host,
        row_ids=fam.row_ids[keep], stratum_live=new_live)
    return out, block


def remap_family_row_ids(fam: SampleFamily,
                         remap: np.ndarray) -> SampleFamily:
    """Re-key a family's physical row ids through a base-table compaction
    remap (types.TableCompaction). Sample CONTENT is untouched — entry keys,
    units, inclusion frequencies, prefixes all stay put, because compaction
    only relabels physical positions of live rows. Every family row is live
    (tombstone passes drop dead sampled rows), so no id maps to -1."""
    if fam.row_ids is None or (fam.row_ids < 0).any():
        # -1 ids are the sentinel merge_family writes for rows of a LEGACY
        # (pre-mutation-support) family — they name no physical row, so
        # there is nothing to remap them through.
        raise ValueError("family has no (or sentinel) row_ids — built "
                         "before mutation support; rebuild it to enable "
                         "base compaction")
    new_ids = np.asarray(remap, dtype=np.int64)[fam.row_ids]
    if (new_ids < 0).any():
        raise ValueError("family holds rows the compaction dropped — "
                         "tombstones were not applied before compacting")
    return fam.lazy_replace(row_ids=new_ids)


@dataclasses.dataclass
class DecayBlock:
    """What one inclusion-frequency decay pass did to a family: the strata it
    reset and the row churn (dropped old sampled rows + freshly admitted
    ones). The striped-block consequence is a full restripe — unlike a
    tombstone pass, decay both removes and ADMITS rows, so there is no small
    scatter that covers it."""
    strata: np.ndarray             # int64: stable stratum ids reset
    n_dropped: int                 # old sampled rows removed (their strata)
    n_admitted: int                # fresh rows admitted under the reset freqs
    epoch: int = 0                 # decay epoch that drew the fresh units


def decay_strata(fam: SampleFamily, tbl: table_lib.Table,
                 strata: np.ndarray, units_full: np.ndarray
                 ) -> tuple[SampleFamily, DecayBlock]:
    """Inclusion-frequency decay (docs/MAINTENANCE.md): reset the inclusion
    frequencies of `strata` to their LIVE counts and resample those strata
    from the base table under fresh entry keys.

    Churn-heavy strata inflate the cumulative inclusion histogram F while
    live rows dwindle: surviving rows keep rate min(1, K/F_cum), so the
    stratum's expected sample size decays to live·K/F_cum even though
    min(live, K) rows could be held. Tombstone passes cannot fix this —
    raising a rate pulls never-materialized base rows IN, which only a pass
    over the base table can supply. This one:

      * drops the family's current rows of the decayed strata,
      * draws fresh units for every LIVE base row of those strata from
        `units_full` (decay_units — indexed by physical row id, so the
        from-scratch oracle reproduces the draw exactly),
      * keys them entry_key = u·F_live and admits entry_key < K₁ — a fresh
        Poisson stratified sample of each stratum, HT rates min(1, K/F_live)
        exact by construction,
      * leaves every other stratum's rows, keys, and rates bit-identical.

    The family's sampled set GROWS back toward min(live, K₁) per stratum —
    restored utilization is the point. Requires the mutation-era metadata
    (row_ids/strata_keys); raises on legacy families.
    """
    if fam.row_ids is None or fam.strata_keys is None or not fam.phi:
        raise ValueError("decay needs a stratified family with mutation "
                         "metadata (row_ids + strata_keys)")
    strata = np.unique(np.asarray(strata, dtype=np.int64))
    new_freqs = fam.stratum_freqs.copy()
    live_freqs = fam.live_freqs
    new_freqs[strata] = live_freqs[strata]

    # Map every base row to the family's STABLE stratum ids.
    mat = np.stack([tbl.host_column(c).astype(np.int32) for c in fam.phi],
                   axis=1)
    codes, keys = table_lib.map_codes_stable(mat, fam.strata_keys)
    if len(keys) != len(fam.strata_keys):
        raise ValueError("table holds strata this family has never seen — "
                         "merge the pending delta before decaying")
    sel = np.isin(codes, strata)
    if tbl.live is not None:
        sel &= tbl.live
    idx = np.flatnonzero(sel).astype(np.int64)

    freqs_f32 = new_freqs.astype(np.float32)
    u = np.asarray(units_full, dtype=np.float32)[idx]
    ek_new = u * freqs_f32[codes[idx]]
    keep_new = ek_new < fam.ks[0]

    keep_old = ~np.isin(fam.row_strata, strata)
    ek_m = np.concatenate([fam.entry_key_host[keep_old], ek_new[keep_new]])
    order = np.argsort(ek_m, kind="stable")
    ek_sorted = ek_m[order]
    prefixes = tuple(count_below(ek_sorted, k) for k in fam.ks)

    def merge_col(old_arr, new_arr):
        old_h = np.asarray(old_arr)[keep_old]
        return np.concatenate(
            [old_h, np.asarray(new_arr, dtype=old_h.dtype)])[order]

    cols_host = {name: merge_col(fam.host_column(name),
                                 tbl.host_column(name)[idx][keep_new])
                 for name in fam.columns}
    old_units = (fam.unit_host if fam.unit_host is not None
                 else np.asarray(fam.unit))
    out = SampleFamily(
        phi=fam.phi, ks=fam.ks,
        columns=None, freq=None, entry_key=None, unit=None,  # lazy mirrors
        prefix_sizes=prefixes, n_rows=int(ek_sorted.size),
        table_rows=fam.table_rows,
        n_distinct=len(new_freqs), stratum_freqs=new_freqs,
        strata_keys=fam.strata_keys,
        row_strata=merge_col(fam.row_strata, codes[idx][keep_new]),
        entry_key_host=ek_sorted, columns_host=cols_host,
        unit_host=merge_col(old_units, u[keep_new]),
        row_ids=merge_col(fam.row_ids, idx[keep_new]),
        stratum_live=fam.stratum_live)
    block = DecayBlock(strata=strata,
                       n_dropped=int((~keep_old).sum()),
                       n_admitted=int(keep_new.sum()))
    return out, block


def stratified_exact_k(tbl: table_lib.Table, phi: Sequence[str], k: int, *,
                       seed: int = 0) -> dict[str, np.ndarray]:
    """Paper-faithful exact-K stratified sample (host reference): for every
    distinct x of φ keep all rows if F(x) <= K else exactly K uniform rows.
    Returns host columns plus `_rate` (per-row sampling rate, §4.3)."""
    codes, _ = table_lib.combined_codes(tbl, phi)
    n_distinct = int(codes.max()) + 1 if len(codes) else 0
    freqs = table_lib.stratum_frequencies(codes, n_distinct)
    rng = np.random.default_rng(seed)
    prio = rng.random(tbl.n_rows)
    # Rank within stratum by random priority; keep rank < K.
    order = np.lexsort((prio, codes))
    ranks = np.empty(tbl.n_rows, dtype=np.int64)
    seen: dict[int, int] = {}
    pos = np.zeros(n_distinct, dtype=np.int64)
    sorted_codes = codes[order]
    # vectorized rank-within-group over the sorted array
    boundaries = np.flatnonzero(np.diff(sorted_codes)) + 1
    starts = np.concatenate([[0], boundaries])
    group_start = np.repeat(starts, np.diff(np.concatenate([starts, [len(codes)]])))
    ranks[order] = np.arange(tbl.n_rows) - group_start
    keep = ranks < k
    rate = np.minimum(1.0, k / freqs[codes])
    out = {name: np.asarray(arr)[keep] for name, arr in tbl.columns.items()}
    out["_rate"] = rate[keep].astype(np.float32)
    return out


def _power_sum(s: float, m: int) -> float:
    """Σ_{r=1..m} r^{-s}: exact partial sum + Euler–Maclaurin tail (supports
    m up to 1e9+ without materializing ranks)."""
    cut = min(m, 1_000_000)
    r = np.arange(1, cut + 1, dtype=np.float64)
    total = float((r ** -s).sum())
    if m > cut:
        a, b = float(cut + 1), float(m)
        if abs(s - 1.0) < 1e-12:
            integral = math.log(b / a)
        else:
            integral = (a ** (1 - s) - b ** (1 - s)) / (s - 1)
        total += integral + 0.5 * (a ** -s + b ** -s) \
            + s / 12.0 * (a ** (-s - 1) - b ** (-s - 1))
    return total


def zipf_storage_fraction(s: float, k: float, m_values: int) -> float:
    """Appendix A / Table 5: storage of S(φ,K) as a fraction of the table when
    φ ~ Zipf(s) with M distinct values and F(x) = M / rank(x)^s.

    (The paper sets the *highest frequency* to M; total table rows are then
    Σ_r M/r^s.)  Σ min(F(r), K) = K·r* + M·Σ_{r>r*} r^{-s} with
    r* = #ranks where F ≥ K = floor((M/K)^{1/s})."""
    m = float(m_values)
    r_star = int(min(m, math.floor((m / k) ** (1.0 / s))))
    head = k * r_star
    tail = m * (_power_sum(s, m_values) - _power_sum(s, r_star)) if r_star < m_values else 0.0
    total = m * _power_sum(s, m_values)
    return float((head + tail) / total)
