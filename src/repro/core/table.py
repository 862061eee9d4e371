"""Columnar, dictionary-encoded tables.

Host side: value dictionaries (numpy object arrays) for categorical columns.
Device side: int32 code / float32 measure arrays, optionally sharded row-wise
across a mesh `data` axis (BlinkDB's HDFS striping, adapted — DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (CmpOp, ColumnKind, ColumnSchema, Predicate,
                              TableCompaction, TableDelta, TableMutation,
                              TableSchema)

# numpy comparator table for host-side predicate evaluation (mirrors
# types.cmp_fns, which is the jnp table used on device)
_NP_CMP = {
    CmpOp.EQ: np.equal, CmpOp.NE: np.not_equal,
    CmpOp.LT: np.less, CmpOp.LE: np.less_equal,
    CmpOp.GT: np.greater, CmpOp.GE: np.greater_equal,
}


class _LazyColumns(dict):
    """Base for device-column dicts whose entries materialize lazily from a
    host mirror on first ACCESS (item/values/items). Subclasses provide the
    stale-key set and the host lookup. Shared by the table- and family-level
    mirrors so the lazy-refresh semantics cannot drift apart.

    Sharp edge (applies to every subclass): dict fast paths that bypass
    `__getitem__` — `dict(d)`, `{**d}`, `d.get(k)` — skip the refresh;
    consumers must stick to the overridden accessors.
    """

    def _stale_keys(self) -> set:
        raise NotImplementedError

    def _host(self, key):
        raise NotImplementedError

    def _refresh(self, key) -> None:
        stale = self._stale_keys()
        if key in stale:
            super().__setitem__(key, jnp.asarray(self._host(key)))
            stale.discard(key)

    def __getitem__(self, key):
        self._refresh(key)
        return super().__getitem__(key)

    def items(self):
        for k in list(super().keys()):
            self._refresh(k)
        return super().items()

    def values(self):
        for k in list(super().keys()):
            self._refresh(k)
        return super().values()


class _LazyDeviceColumns(_LazyColumns):
    """Table-level lazy mirror: `Table.append` only touches the host mirrors
    and marks the column stale; the device copy refreshes on first access.
    The sampled serving path never reads full base-table columns — only the
    exact path and join gathers do — so steady-state ingest costs O(delta)
    in host→device traffic instead of re-uploading the table each epoch.
    """

    def __init__(self, mapping, owner: "Table"):
        super().__init__(mapping)
        self._owner = owner

    def _stale_keys(self) -> set:
        return self._owner._stale_device

    def _host(self, key):
        return self._owner.columns_host[key]


@dataclasses.dataclass
class Table:
    schema: TableSchema
    # column name -> device array: int32 codes (categorical) / f32 (numeric)
    columns: dict[str, jax.Array]
    # column name -> numpy array of dictionary values (categoricals only)
    dictionaries: dict[str, np.ndarray]
    n_rows: int
    # host mirrors of the encoded schema columns — the append/merge path is
    # host-side, and without a mirror every epoch would read the full device
    # columns back (O(table), not O(delta), in host↔device traffic on
    # accelerator backends).
    columns_host: dict[str, np.ndarray] | None = None
    # host tombstone mask: live[i] False once physical row i is deleted or
    # superseded by an update. None means every row is live (append-only
    # tables pay nothing). Physical rows NEVER move — a row's physical index
    # is the stable id the sampling layer keys inclusion metadata on; dead
    # slots are reclaimed only by striped-block compaction, not here.
    live: np.ndarray | None = None
    # columns whose device copy lags the host mirror (lazy re-upload)
    _stale_device: set = dataclasses.field(default_factory=set, repr=False)
    _live_count: int | None = dataclasses.field(default=None, repr=False)
    _live_device: jax.Array | None = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        if not isinstance(self.columns, _LazyDeviceColumns):
            self.columns = _LazyDeviceColumns(self.columns, self)

    @property
    def n_live(self) -> int:
        """Live (non-tombstoned) rows; == n_rows for append-only tables."""
        if self.live is None:
            return self.n_rows
        if self._live_count is None:
            self._live_count = int(self.live.sum())
        return self._live_count

    def live_mask_device(self) -> jax.Array:
        """Device mirror of the tombstone mask (exact-path predicate AND).
        Cached; invalidated by delete/update/append."""
        if self._live_device is None:
            mask = (np.ones(self.n_rows, dtype=bool) if self.live is None
                    else self.live)
            self._live_device = jnp.asarray(mask)
        return self._live_device

    def host_column(self, name: str) -> np.ndarray:
        if self.columns_host is not None and name in self.columns_host:
            return self.columns_host[name]
        return np.asarray(self.columns[name])

    def column_codes(self, name: str) -> jax.Array:
        return self.columns[name]

    def cardinality(self, name: str) -> int:
        return self.schema.column(name).cardinality

    def encode_value(self, name: str, value) -> int:
        """Host-side: map a raw categorical value to its dictionary code."""
        d = self.dictionaries[name]
        idx = np.nonzero(d == value)[0]
        if idx.size == 0:
            return -1  # matches no row
        return int(idx[0])

    def decode_value(self, name: str, code: int):
        return self.dictionaries[name][code]

    def row_bytes(self) -> int:
        return 4 * len(self.columns)

    @property
    def nbytes(self) -> int:
        return self.row_bytes() * self.n_rows

    def append(self, raw: Mapping[str, np.ndarray]) -> TableDelta:
        """Append-only ingestion: encode a delta of host rows against the
        existing dictionaries and concatenate onto the device columns.

        Incremental by construction — existing rows are never recoded:
        categorical values already in a dictionary keep their code, unseen
        values get fresh codes past the current cardinality (the dictionary
        is extended, not rebuilt). Returns the TableDelta the sampling layer
        needs to merge materialized families (docs/MAINTENANCE.md).
        """
        schema_cols = set(self.schema.column_names)
        got = set(raw.keys())
        if got != schema_cols:
            raise ValueError(
                f"append to {self.schema.name!r}: delta columns {sorted(got)} "
                f"!= schema columns {sorted(schema_cols)}")
        # Validate AND encode the whole delta before mutating anything — a
        # rejection (ragged lengths, a measure that won't cast to f32) must
        # not leave phantom dictionary entries or inflated cardinality.
        n_delta = None
        encoded: dict[str, np.ndarray] = {}
        new_dict_values: dict[str, np.ndarray] = {}
        for cname in self.schema.column_names:
            values = np.asarray(raw[cname])
            if n_delta is None:
                n_delta = len(values)
            elif len(values) != n_delta:
                raise ValueError(
                    f"column {cname}: length {len(values)} != {n_delta}")
            if self.schema.column(cname).kind is ColumnKind.CATEGORICAL:
                encoded[cname], new_dict_values[cname] = _encode_against(
                    values, self.dictionaries[cname])
            else:
                encoded[cname] = values.astype(np.float32)
        # ---- commit point: nothing below raises ----
        # Gathered join attributes ("dim.col") cannot ride a schema-only
        # delta; leaving them at the old length would corrupt the exact/join
        # paths. Strip here (the engine lazily regathers on next use).
        for c in [c for c in self.columns if "." in c]:
            del self.columns[c]
            if self.columns_host is not None:
                self.columns_host.pop(c, None)
        for cname, new_vals in new_dict_values.items():
            if new_vals.size:
                self.dictionaries[cname] = np.concatenate(
                    [self.dictionaries[cname], new_vals])
                self.schema = self.schema.with_cardinality(
                    cname, len(self.dictionaries[cname]))
        delta = TableDelta(self.schema.name, self.n_rows, int(n_delta or 0),
                           encoded, new_dict_values)
        if self.columns_host is None:
            self.columns_host = {}
        for cname, arr in encoded.items():
            # Host-side concat on the mirror only; the device copy refreshes
            # lazily on access (an eager per-epoch re-upload — or an
            # on-device concat, which compiles a new XLA program per length —
            # would make ingest O(table) again).
            self.columns_host[cname] = np.concatenate(
                [self.host_column(cname), arr])
            self._stale_device.add(cname)
        self.n_rows += delta.n_rows
        if self.live is not None:
            self.live = np.concatenate(
                [self.live, np.ones(delta.n_rows, dtype=bool)])
        self._live_count = None
        self._live_device = None
        return delta

    def eval_predicate_host(self, pred: Predicate) -> np.ndarray:
        """Host-side DNF predicate evaluation over the encoded columns.

        Categorical atoms compare dictionary CODES against the encoded value
        (-1 for values the dictionary has never seen) — numerically, exactly
        as the device path does after bind_predicate, so a host mutation and
        a device scan agree on which rows match.
        """
        cols_f32: dict[str, np.ndarray] = {}   # one cast per column, not atom
        disj = np.zeros(self.n_rows, dtype=bool)
        for conj in pred.disjuncts:
            m = np.ones(self.n_rows, dtype=bool)
            for a in conj.atoms:
                if self.schema.column(a.column).kind is ColumnKind.CATEGORICAL:
                    enc = float(self.encode_value(a.column, a.value))
                else:
                    enc = float(a.value)
                col = cols_f32.get(a.column)
                if col is None:
                    col = self.host_column(a.column).astype(np.float32)
                    cols_f32[a.column] = col
                m &= _NP_CMP[a.op](col, np.float32(enc))
            disj |= m
        return disj

    def _matched_live(self, predicate: Predicate) -> np.ndarray:
        match = self.eval_predicate_host(predicate)
        if self.live is not None:
            match &= self.live
        return np.flatnonzero(match).astype(np.int64)

    def _tombstone(self, idx: np.ndarray) -> None:
        if not idx.size:
            return   # no-match mutation: stay on the live-is-None fast paths
        if self.live is None:
            self.live = np.ones(self.n_rows, dtype=bool)
        self.live[idx] = False
        self._live_count = None
        self._live_device = None

    def delete(self, predicate: Predicate) -> TableMutation:
        """Tombstone every live row matching `predicate`.

        Rows are marked dead in the host mask, never moved: physical indices
        stay stable (the id scheme the sample-maintenance layer relies on),
        and the dead slots are reclaimed by striped-block compaction, not by
        rewriting the table. Returns the TableMutation the sampling layer
        needs to ghost its copies and decrement live stratum counts.
        """
        idx = self._matched_live(predicate)
        tomb_cols = {c: self.host_column(c)[idx].copy()
                     for c in self.schema.column_names}
        self._tombstone(idx)
        return TableMutation(self.schema.name, idx, tomb_cols, None)

    def update(self, predicate: Predicate, assignments: Mapping) -> TableMutation:
        """Update matching live rows: tombstone the old versions and append
        re-encoded copies with `assignments` applied (LSM-style
        tombstone+insert, so updates ride the existing delta machinery).

        `assignments` maps column name -> new RAW value (scalar, broadcast to
        every matched row, or an array of per-row values). Categorical
        assignments may introduce new dictionary values — the dictionary
        extends exactly as for an append. Atomic: the delta is validated and
        committed by `append` BEFORE any row is tombstoned, so a rejected
        assignment leaves the table untouched.
        """
        unknown = set(assignments) - set(self.schema.column_names)
        if unknown:
            raise KeyError(f"update assigns unknown columns {sorted(unknown)}")
        idx = self._matched_live(predicate)
        tomb_cols = {c: self.host_column(c)[idx].copy()
                     for c in self.schema.column_names}
        raw: dict[str, np.ndarray] = {}
        for cname in self.schema.column_names:
            if cname in assignments:
                vals = np.asarray(assignments[cname])
                if vals.ndim == 0:
                    vals = np.full(len(idx), vals[()])
                elif len(vals) != len(idx):
                    raise ValueError(
                        f"assignment {cname}: length {len(vals)} != "
                        f"{len(idx)} matched rows")
                raw[cname] = vals
            elif self.schema.column(cname).kind is ColumnKind.CATEGORICAL:
                # decode so append re-encodes against the (same) dictionary
                raw[cname] = self.dictionaries[cname][tomb_cols[cname]]
            else:
                raw[cname] = tomb_cols[cname]
        delta = self.append(raw) if len(idx) else None
        self._tombstone(idx)
        return TableMutation(self.schema.name, idx, tomb_cols, delta)

    def compact(self) -> TableCompaction | None:
        """Physically drop every tombstoned row — the base-table compaction
        epoch (docs/MAINTENANCE.md). This is the ONE place physical rows
        move: every row id changes, so the returned remap (old id -> new id,
        -1 for dropped rows) must be shipped to every layer keying on
        physical ids before the table is used again — `BlinkDB.compact_table`
        drives that. Live rows keep their relative order, so remapped sorted
        id arrays stay sorted. Dictionaries are untouched (codes never move;
        a value whose rows all died keeps its code at zero frequency).

        Host-only: the compacted columns land in the host mirrors and the
        device copies refresh lazily on next access, exactly like an append —
        the sampled serving path never reads base columns, so steady-state
        reclamation ships no device traffic of its own. Returns None when
        there is nothing to reclaim (no tombstones).
        """
        if self.live is None or self.n_live == self.n_rows:
            return None
        live = self.live
        n_before = self.n_rows
        remap = np.where(live, np.cumsum(live) - 1, -1).astype(np.int64)
        # Gathered join attributes ("dim.col") are device-only columns of the
        # old physical length — strip them (the engine regathers lazily),
        # mirroring Table.append's schema-only-delta rule.
        for c in [c for c in self.columns if "." in c]:
            del self.columns[c]
            if self.columns_host is not None:
                self.columns_host.pop(c, None)
        if self.columns_host is None:
            self.columns_host = {}
        for cname in self.schema.column_names:
            self.columns_host[cname] = self.host_column(cname)[live]
            self._stale_device.add(cname)
        self.n_rows = int(live.sum())
        self.live = None
        self._live_count = None
        self._live_device = None
        return TableCompaction(self.schema.name, remap, n_before,
                               n_before - self.n_rows)


def get_or_assign_codes(keys: list, lookup: dict) -> tuple[np.ndarray, list]:
    """Shared get-or-assign-next-code kernel for every incremental encoding
    path (dictionary extension, stable stratum mapping, cross-dictionary
    code alignment): keys already in `lookup` keep their code, unseen keys
    get fresh codes past len(lookup) in first-appearance order. Returns
    (int64 codes per key, the new keys)."""
    out = np.empty(len(keys), dtype=np.int64)
    new_keys = []
    next_code = len(lookup)
    for j, k in enumerate(keys):
        code = lookup.get(k)
        if code is None:
            code = next_code
            next_code += 1
            lookup[k] = code
            new_keys.append(k)
        out[j] = code
    return out, new_keys


def _encode_against(values: np.ndarray, dictionary: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Encode raw categorical values against an existing dictionary.
    Returns (int32 codes, new values in first-appearance-of-np.unique order).
    The dictionary is NOT assumed sorted (appends break global sort)."""
    uniq, inverse = np.unique(values, return_inverse=True)
    lookup = {v: i for i, v in enumerate(dictionary.tolist())}
    uniq_codes, new_vals = get_or_assign_codes(uniq.tolist(), lookup)
    if new_vals:
        # Same-kind values keep their natural dtype so the later concatenate
        # PROMOTES the dictionary width — forcing dictionary.dtype would
        # silently truncate a string longer than any existing entry.
        new_arr = np.asarray(new_vals)
        if new_arr.dtype.kind != dictionary.dtype.kind:
            new_arr = new_arr.astype(dictionary.dtype)
    else:
        new_arr = np.empty(0, dtype=dictionary.dtype)
    return uniq_codes[inverse].astype(np.int32), new_arr


def from_columns(name: str, raw: Mapping[str, np.ndarray],
                 categorical: Sequence[str] | None = None) -> Table:
    """Ingest host columns. Columns with non-float dtypes (or listed in
    `categorical`) are dictionary-encoded; the rest become float32 measures."""
    categorical = set(categorical or ())
    n_rows = None
    schemas, cols, dicts, hosts = [], {}, {}, {}
    for cname, values in raw.items():
        values = np.asarray(values)
        if n_rows is None:
            n_rows = len(values)
        elif len(values) != n_rows:
            raise ValueError(f"column {cname}: length {len(values)} != {n_rows}")
        is_cat = cname in categorical or not np.issubdtype(values.dtype, np.floating)
        if is_cat:
            uniq, codes = _unique_inverse(values)
            schemas.append(ColumnSchema(cname, ColumnKind.CATEGORICAL, len(uniq)))
            hosts[cname] = codes.astype(np.int32)
            cols[cname] = jnp.asarray(hosts[cname])
            dicts[cname] = uniq
        else:
            schemas.append(ColumnSchema(cname, ColumnKind.NUMERIC))
            hosts[cname] = values.astype(np.float32)
            cols[cname] = jnp.asarray(hosts[cname])
    return Table(TableSchema(name, tuple(schemas)), cols, dicts,
                 int(n_rows or 0), columns_host=hosts)


def combined_codes(table: Table, phi: Sequence[str]) -> tuple[np.ndarray, np.ndarray]:
    """Dense group ids for the value-combinations of column set φ.

    Returns (codes[n_rows] int64 dense in [0, n_distinct), key_matrix
    [n_distinct, len(phi)] of per-column dictionary codes for decoding).
    Host-assisted (np.unique) — this runs in the *offline* sample-creation
    path, mirroring BlinkDB's offline Hive jobs (DESIGN.md §2).
    """
    phi = sorted(phi)
    if not phi:
        n = table.n_rows
        return np.zeros(n, dtype=np.int64), np.zeros((1, 0), dtype=np.int32)
    cols = [table.host_column(c) for c in phi]
    fast = _row_ids(cols)
    if fast is not None:
        return fast
    mats = np.stack(cols, axis=1)
    uniq, inverse = np.unique(mats, axis=0, return_inverse=True)
    return inverse.astype(np.int64), uniq.astype(np.int32)


def _row_ids(cols: list[np.ndarray]
             ) -> tuple[np.ndarray, np.ndarray] | None:
    """np.unique(rows, axis=0, return_inverse=True) without its structured
    row sort, for rows of non-negative integer columns: returns (int64
    inverse, int32 distinct rows), or None when a column is not such a code
    column or the key below would overflow int64.

    One mixed-radix int64 key per row (first column most significant)
    orders rows exactly as np.unique's lexicographic comparison does."""
    if not len(cols[0]) or any(c.dtype.kind not in "iu" for c in cols):
        return None
    if any(int(c.min()) < 0 for c in cols):
        return None
    radix = [int(c.max()) + 1 for c in cols]
    if np.prod(radix, dtype=float) >= 2.0 ** 62:
        return None
    key = np.zeros(len(cols[0]), dtype=np.int64)
    for c, r in zip(cols, radix):
        key = key * r + c
    space = int(np.prod(radix))
    if space <= max(1 << 22, 2 * len(key)):
        seen = np.flatnonzero(np.bincount(key, minlength=space))
        lut = np.empty(space, dtype=np.int64)
        lut[seen] = np.arange(len(seen))
        inverse = lut[key]
    else:
        seen, inverse = np.unique(key, return_inverse=True)
    uniq = np.empty((len(seen), len(cols)), dtype=np.int32)
    rest = seen
    for j in range(len(cols) - 1, -1, -1):
        rest, uniq[:, j] = np.divmod(rest, radix[j])
    return inverse.astype(np.int64), uniq


def _unique_inverse(values: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """np.unique(values, return_inverse=True) for a categorical column,
    without a comparison sort where the values allow it: non-negative ints
    directly, and fixed-width strings as rows of per-position code-point
    ranks (NumPy orders str by code point, a shorter string first)."""
    cols = None
    if values.dtype.kind in "iu":
        cols = [values]
    elif values.dtype.kind == "U" and values.size:
        width = values.dtype.itemsize // 4
        cps = np.ascontiguousarray(values).view(np.uint32).reshape(-1, width)
        cols = []
        for j in range(width):
            seen = np.bincount(cps[:, j]) > 0
            if seen.sum() > 1:   # a constant position orders nothing
                rank = (np.cumsum(seen) - 1).astype(np.int32)
                cols.append(rank[cps[:, j]])
    fast = _row_ids(cols) if cols else None
    if fast is None:
        return np.unique(values, return_inverse=True)
    inverse, uniq_rows = fast
    first = np.empty(len(uniq_rows), dtype=np.int64)
    first[inverse[::-1]] = np.arange(len(values) - 1, -1, -1)
    return values[first], inverse


def stratum_frequencies(codes: np.ndarray, n_distinct: int) -> np.ndarray:
    """F(φ, T, x): per-stratum row counts."""
    return np.bincount(codes, minlength=n_distinct).astype(np.int64)


def map_codes_stable(mat: np.ndarray, key_matrix: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Map delta rows to STABLE stratum ids given an existing key matrix.

    `combined_codes` numbers strata by np.unique's lexicographic order, which
    renumbers everything when new value-combinations appear — useless for
    incremental maintenance. This maps each row of `mat` [d, w] (per-column
    dictionary codes on φ) through `key_matrix` [D, w] (row i = the codes of
    stratum i): known combinations keep their id, unseen ones get fresh ids
    D, D+1, ... Returns (int64 codes[d], extended key matrix).
    """
    w = key_matrix.shape[1]
    if w == 0:  # φ = ∅: single stratum
        return np.zeros(len(mat), dtype=np.int64), key_matrix
    uniq, inverse = np.unique(mat, axis=0, return_inverse=True)
    lookup = {tuple(r): i for i, r in enumerate(key_matrix.tolist())}
    ids, new_rows = get_or_assign_codes([tuple(r) for r in uniq.tolist()],
                                        lookup)
    if new_rows:
        key_matrix = np.concatenate(
            [key_matrix, np.asarray(new_rows, dtype=np.int32).reshape(-1, w)])
    return ids[inverse].astype(np.int64), key_matrix


def extend_frequencies(old_freqs: np.ndarray, delta_codes: np.ndarray,
                       n_distinct: int) -> np.ndarray:
    """Incremental F update: old per-stratum counts (padded with zeros for
    strata first seen in the delta) plus the delta's histogram. Append-only,
    so frequencies are monotone non-decreasing — the invariant the merge
    path's entry-key rescaling relies on (rows only ever LEAVE a prefix)."""
    out = np.zeros(n_distinct, dtype=np.int64)
    out[: len(old_freqs)] = old_freqs
    out += np.bincount(delta_codes, minlength=n_distinct).astype(np.int64)
    return out
