"""Full mutation lifecycle: tombstone deletes/updates, ghost-row compaction
epochs, storage-reclamation epochs (base-table compaction +
inclusion-frequency decay), and a randomized mutation-sequence harness
(docs/MAINTENANCE.md).

The load-bearing property extends the PR-2 append oracle to ARBITRARY
insert/delete/update/sample-compact/base-compact/decay interleavings: after
any mutation sequence the incrementally maintained family must be
bit-identical to `build_family` on the row HISTORY (every row ever inserted
— base compaction physically drops dead base rows, so the oracle rebuilds
from a shadow history table and re-keys its row ids through the composed
compaction remap) with the per-epoch unit segments (decay epochs overwrite
the affected rows' units with the deterministic decay stream) and inclusion
frequencies that are CUMULATIVE except where a decay reset them (the mirror
"forgives" exactly the dead rows each decayed stratum held at decay time).
Plus cache validity: neither tombstones, a geometry-preserving compaction,
nor a base compaction may drop — or worse, serve stale — a compiled query
program.

The hypothesis harness is optional (importorskip-style guard, matching
tests/test_properties.py); the deterministic interleavings below it run in
tier-1 regardless.
"""
import os

import jax
import numpy as np
import pytest

from repro.core import (AggOp, Atom, BlinkDB, CmpOp, EngineConfig, ErrorBound,
                        Predicate, Query, QueryTemplate)
from repro.core import executor as exec_lib
from repro.core import sampling as samp
from repro.core import table as table_lib
from repro.core.maintenance import MaintenanceConfig, SampleMaintainer
from repro.data import synth

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dep: skip the randomized harness only
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                                      reason="hypothesis not installed")


# ------------------------------------------------------------- table layer

def test_delete_tombstones_without_moving_rows():
    tbl = table_lib.from_columns("t", {
        "key": np.array(["a", "b", "a", "c"]),
        "x": np.array([1., 2., 3., 4.], np.float32)})
    mut = tbl.delete(Predicate.where(Atom("key", CmpOp.EQ, "a")))
    np.testing.assert_array_equal(mut.tombstoned, [0, 2])
    np.testing.assert_array_equal(mut.tombstoned_columns["x"], [1., 3.])
    assert mut.delta is None
    # physical layout untouched: codes, dictionaries, lengths all stable
    assert tbl.n_rows == 4 and tbl.n_live == 2
    np.testing.assert_array_equal(tbl.live, [False, True, False, True])
    np.testing.assert_array_equal(tbl.host_column("x"), [1., 2., 3., 4.])
    # deleting again matches nothing (rows already dead)
    assert tbl.delete(Predicate.where(Atom("key", CmpOp.EQ, "a"))).n_tombstoned == 0
    # unseen dictionary value matches nothing rather than erroring
    assert tbl.delete(Predicate.where(Atom("key", CmpOp.EQ, "zz"))).n_tombstoned == 0


def test_update_is_tombstone_plus_reinsert():
    tbl = table_lib.from_columns("t", {
        "key": np.array(["a", "b", "a"]),
        "x": np.array([1., 2., 3.], np.float32)})
    mut = tbl.update(Predicate.where(Atom("key", CmpOp.EQ, "a")), {"key": "z"})
    np.testing.assert_array_equal(mut.tombstoned, [0, 2])
    assert mut.delta is not None and mut.delta.n_rows == 2
    assert mut.delta.start_row == 3
    # new versions appended with the assignment applied, measures carried over
    assert tbl.n_rows == 5 and tbl.n_live == 3
    assert list(mut.delta.new_dict_values["key"]) == ["z"]
    np.testing.assert_array_equal(tbl.host_column("x")[3:], [1., 3.])
    z = tbl.encode_value("key", "z")
    np.testing.assert_array_equal(tbl.host_column("key")[3:], [z, z])
    np.testing.assert_array_equal(tbl.live, [False, True, False, True, True])


def test_update_rejects_bad_assignment_atomically():
    tbl = table_lib.from_columns("t", {
        "key": np.array(["a", "b"]), "x": np.array([1., 2.], np.float32)})
    with pytest.raises(KeyError, match="unknown columns"):
        tbl.update(Predicate.where(Atom("key", CmpOp.EQ, "a")), {"nope": 1})
    with pytest.raises(ValueError):
        tbl.update(Predicate.where(Atom("key", CmpOp.EQ, "a")),
                   {"x": np.array(["oops"])})  # won't cast to f32
    # the failed update must not have tombstoned or appended anything
    assert tbl.n_rows == 2 and tbl.n_live == 2 and tbl.live is None


def test_host_predicate_matches_device_encoding_semantics():
    """eval_predicate_host compares dictionary codes numerically — exactly
    what the device path does after bind_predicate (unseen values encode to
    -1: EQ matches nothing, NE everything, GT everything with codes >= 0)."""
    tbl = table_lib.from_columns("t", {
        "key": np.array(["b", "a", "c"]),
        "x": np.array([1., 2., 3.], np.float32)})
    m = tbl.eval_predicate_host(Predicate.where(Atom("key", CmpOp.NE, "zz")))
    np.testing.assert_array_equal(m, [True, True, True])
    m = tbl.eval_predicate_host(Predicate.where(Atom("x", CmpOp.GE, 2.0)))
    np.testing.assert_array_equal(m, [False, True, True])
    m = tbl.eval_predicate_host(Predicate((
        Predicate.where(Atom("key", CmpOp.EQ, "a")).disjuncts[0],
        Predicate.where(Atom("x", CmpOp.GT, 2.5)).disjuncts[0])))
    np.testing.assert_array_equal(m, [False, True, True])


# --------------------------------------------- mutation harness scaffolding

SEED = 11


def _mk_db(n0=4000, k1=300.0, seed=SEED, **synth_kw):
    synth_kw.setdefault("n_cities", 50)
    tbl = table_lib.from_columns("s", synth.sessions_table(n0, seed=7,
                                                           **synth_kw))
    db = BlinkDB(EngineConfig(k1=k1, m=3, seed=seed))
    db.register_table("s", tbl)
    db.add_family("s", ("City",))
    db.add_family("s", ())
    return db


def _clone_table(tbl: table_lib.Table) -> table_lib.Table:
    """Host-side snapshot of a table (the mirror's shadow history table —
    it only ever runs host paths: append/delete/update/host_column)."""
    cols = {c: None for c in tbl.schema.column_names}
    out = table_lib.Table(
        tbl.schema, cols,
        {k: v.copy() for k, v in tbl.dictionaries.items()},
        tbl.n_rows,
        columns_host={c: np.array(tbl.host_column(c))
                      for c in tbl.schema.column_names},
        live=None if tbl.live is None else tbl.live.copy())
    out._stale_device = set(tbl.schema.column_names)
    return out


class MutationMirror:
    """Drives engine mutations while recording everything the from-scratch
    oracle needs after every step: the per-row unit vector (append segments,
    overwritten by decay draws), a shadow HISTORY table holding every row
    ever inserted (base compaction drops dead rows from the real table but
    the inclusion-frequency story is defined over the history), the composed
    history→current row-id remap, and per-stratum decay "forgiveness" (how
    many dead rows each decayed stratum shed from its inclusion count)."""

    def __init__(self, db: BlinkDB, table: str = "s"):
        self.db, self.table = db, table
        tbl = db.tables[table]
        n0 = tbl.n_rows
        seed = db.config.seed
        # Per-FAMILY unit vectors: append epochs extend every stratified
        # family with the same shared delta draw, but a decay redraws units
        # for ONE family's strata — afterwards the families' streams diverge.
        self.units = {phi: samp.base_units(n0, seed)
                      for phi in db.families[table] if phi}
        self.uunits = samp.base_units(n0, seed, uniform=True)
        self.history = _clone_table(tbl)
        self.h2c = np.arange(n0, dtype=np.int64)   # history id -> current id
        # phi -> {stratum key tuple: dead rows forgiven at last decay}
        self.forgiven: dict[tuple[str, ...], dict[tuple, int]] = {}

    def _draw(self, d: int, epoch: int) -> None:
        seed = self.db.config.seed
        seg = samp.delta_units(d, seed, epoch)
        self.units = {phi: np.concatenate([u, seg])
                      for phi, u in self.units.items()}
        self.uunits = np.concatenate(
            [self.uunits, samp.delta_units(d, seed, epoch, uniform=True)])

    def _extend_remap(self, start_row: int, d: int) -> None:
        self.h2c = np.concatenate(
            [self.h2c, start_row + np.arange(d, dtype=np.int64)])

    def append(self, raw):
        rep = self.db.append_rows(self.table, raw)
        self.history.append(raw)
        self._extend_remap(rep.delta.start_row, rep.delta.n_rows)
        self._draw(rep.delta.n_rows, rep.epoch)
        return rep

    def delete(self, pred):
        rep = self.db.delete_rows(self.table, pred)
        self.history.delete(pred)
        return rep

    def update(self, pred, assignments):
        rep = self.db.update_rows(self.table, pred, assignments)
        self.history.update(pred, assignments)
        if rep.epoch is not None:
            self._extend_remap(rep.mutation.delta.start_row,
                               rep.mutation.delta.n_rows)
            self._draw(rep.mutation.delta.n_rows, rep.epoch)
        return rep

    def compact(self):
        return [phi for phi in list(self.db.ghost_fractions(self.table))
                if self.db.compact_family(self.table, phi)]

    def base_compact(self):
        comp = self.db.compact_table(self.table)
        if comp is not None:
            self.h2c = np.where(self.h2c >= 0,
                                comp.remap[np.maximum(self.h2c, 0)], -1)
        return comp

    def decay(self, ratio: float = 1.5):
        """Engine decay of every over-ratio stratum (the maintainer policy),
        mirrored into the oracle state: the affected LIVE history rows take
        their units from the deterministic decay stream (indexed by CURRENT
        physical id), and each decayed stratum forgives exactly the dead
        rows it held right now."""
        from repro.core.maintenance import strata_to_decay
        tbl = self.db.tables[self.table]
        out = {}
        for phi in list(self.db.families[self.table]):
            fam = self.db.families[self.table][phi]
            strata = strata_to_decay(fam, ratio)
            if not strata.size:
                continue
            keys = [tuple(int(v) for v in fam.strata_keys[s])
                    for s in strata]
            block = self.db.decay_family(self.table, phi, strata)
            draw = samp.decay_units(tbl.n_rows, self.db.config.seed,
                                    block.epoch)
            # history rows of the decayed strata, via stable stratum ids
            mat = np.stack([self.history.host_column(c).astype(np.int32)
                            for c in phi], axis=1)
            codes, _ = table_lib.map_codes_stable(mat, fam.strata_keys)
            member = np.isin(codes, strata)
            live = (self.history.live if self.history.live is not None
                    else np.ones(self.history.n_rows, dtype=bool))
            alive = np.flatnonzero(member & live)
            self.units[phi][alive] = draw[self.h2c[alive]]
            fg = self.forgiven.setdefault(phi, {})
            for s, key in zip(strata, keys):
                fg[key] = int((member & ~live
                               & (codes == s)).sum())
            out[phi] = strata
        return out

    def oracle(self, phi: tuple[str, ...]) -> samp.SampleFamily:
        """From-scratch rebuild on the row HISTORY: same units (decay draws
        included), inclusion frequencies cumulative minus forgiveness, same
        caps — then row ids re-keyed into CURRENT physical coordinates
        through the composed compaction remap."""
        hist = self.history
        fam = self.db.families[self.table][phi]
        if phi == ():
            ofam = samp.build_uniform_family(
                hist, 0.0, m=len(fam.ks), units=self.uunits,
                k1=fam.ks[0], cumulative_inclusion=True)
        else:
            codes, key_matrix = table_lib.combined_codes(hist, phi)
            nd = int(codes.max()) + 1 if len(codes) else 0
            incl = table_lib.stratum_frequencies(codes, nd)
            for key, dead in self.forgiven.get(phi, {}).items():
                i = np.flatnonzero(
                    (key_matrix == np.asarray(key, np.int32)).all(axis=1))
                assert i.size == 1, (key, key_matrix)
                incl[i[0]] -= dead
            ofam = samp.build_family(
                hist, phi, k1=fam.ks[0], m=len(fam.ks),
                units=self.units[phi], incl_freqs=incl)
        new_ids = self.h2c[ofam.row_ids]
        assert (new_ids >= 0).all(), "oracle sampled a dropped row"
        return ofam.lazy_replace(row_ids=new_ids)

    def check(self):
        for phi in self.db.families[self.table]:
            _assert_matches_oracle(self.db.families[self.table][phi],
                                   self.oracle(phi))


def _canon(fam):
    """Canonical total row order: (entry_key, physical row id) — row ids are
    unique, so any two families holding the same rows sort identically even
    through exact f32 entry-key ties."""
    return np.lexsort((fam.row_ids, fam.entry_key_host))


def _assert_matches_oracle(fam, oracle):
    assert fam.n_rows == oracle.n_rows
    assert fam.prefix_sizes == oracle.prefix_sizes
    assert fam.table_rows == oracle.table_rows
    np.testing.assert_array_equal(fam.entry_key_host, oracle.entry_key_host)
    # exact per-stratum accounting, both inclusion and live
    np.testing.assert_array_equal(np.sort(fam.stratum_freqs),
                                  np.sort(oracle.stratum_freqs))
    np.testing.assert_array_equal(np.sort(fam.live_freqs),
                                  np.sort(oracle.live_freqs))
    pa, pb = _canon(fam), _canon(oracle)
    np.testing.assert_array_equal(fam.row_ids[pa], oracle.row_ids[pb])
    np.testing.assert_array_equal(fam.unit_host[pa], oracle.unit_host[pb])
    np.testing.assert_array_equal(np.asarray(fam.freq)[pa],
                                  np.asarray(oracle.freq)[pb])
    for c in fam.columns:
        np.testing.assert_array_equal(fam.host_column(c)[pa],
                                      oracle.host_column(c)[pb])
    # bit-identical ESTIMATES at every resolution: identical rows in an
    # identical canonical order make every downstream float reduction equal
    # bit-for-bit, not just approximately
    for k in fam.ks:
        np.testing.assert_array_equal(_ht_moments(fam, k),
                                      _ht_moments(oracle, k))


def _ht_moments(fam, k, group_col="OS", value_col="SessionTime"):
    """Canonical-order HT sufficient statistics (count/sum per group) — the
    host analogue of one fused scan at resolution k."""
    order = _canon(fam)
    ek = fam.entry_key_host[order]
    n = int(np.searchsorted(ek, np.float32(k), side="left"))
    idx = order[:n]
    freq = np.asarray(fam.freq)[idx]
    w = 1.0 / np.minimum(1.0, np.float32(k) / freq).astype(np.float64)
    g = fam.host_column(group_col)[idx].astype(np.int64)
    x = fam.host_column(value_col)[idx].astype(np.float64)
    gmax = int(g.max()) + 1 if n else 1
    return np.stack([np.bincount(g, weights=w, minlength=gmax),
                     np.bincount(g, weights=w * x, minlength=gmax)])


def _apply_op(mirror: MutationMirror, op) -> None:
    tbl = mirror.db.tables[mirror.table]
    kind = op[0]
    if kind == "append":
        _, n, seed = op
        mirror.append(synth.sessions_table(n, seed=seed, n_cities=50))
    elif kind == "delete":
        _, col, idx = op
        vals = tbl.dictionaries[col]
        mirror.delete(Predicate.where(
            Atom(col, CmpOp.EQ, vals[idx % len(vals)])))
    elif kind == "update":
        _, col, idx, assign = op
        vals = tbl.dictionaries[col]
        pred = Predicate.where(Atom(col, CmpOp.EQ, vals[idx % len(vals)]))
        if assign % 2:
            mirror.update(pred, {"City": f"upd{assign}"})
        else:
            mirror.update(pred, {"Bitrate": 100.0 + assign})
    elif kind == "compact":
        mirror.compact()
    elif kind == "basecompact":
        mirror.base_compact()
    elif kind == "decay":
        mirror.decay(ratio=1.5)
    else:                                    # pragma: no cover
        raise AssertionError(op)


# ------------------------------------- randomized harness (hypothesis-only)

if HAVE_HYPOTHESIS:
    _ops = st.one_of(
        st.tuples(st.just("append"), st.integers(20, 400),
                  st.integers(0, 10 ** 6)),
        st.tuples(st.just("delete"), st.sampled_from(["City", "OS", "dt"]),
                  st.integers(0, 60)),
        st.tuples(st.just("update"), st.sampled_from(["City", "OS"]),
                  st.integers(0, 60), st.integers(0, 5)),
        st.tuples(st.just("compact")),
        st.tuples(st.just("basecompact")),
        st.tuples(st.just("decay")),
    )

    @needs_hypothesis
    @settings(max_examples=int(os.environ.get("MUTATION_EXAMPLES", "12")),
              deadline=None)
    @given(seq=st.lists(_ops, min_size=1, max_size=6))
    def test_randomized_mutation_sequences_match_oracle(seq):
        """Any interleaving of append/delete/update/sample-compact/
        base-compact/decay leaves every family bit-identical to the
        from-scratch rebuild oracle — checked after EVERY step, so a bad
        intermediate state can't cancel out."""
        mirror = MutationMirror(_mk_db(n0=2500))
        mirror.check()
        for op in seq:
            _apply_op(mirror, op)
            mirror.check()


# -------------------------------- deterministic interleavings (tier-1 safe)

def _random_op(rng: np.random.Generator):
    kind = rng.choice(["append", "delete", "update", "compact",
                       "basecompact", "decay"],
                      p=[.25, .25, .25, .09, .08, .08])
    if kind == "append":
        return ("append", int(rng.integers(20, 400)),
                int(rng.integers(10 ** 6)))
    if kind == "delete":
        return ("delete", str(rng.choice(["City", "OS", "dt"])),
                int(rng.integers(0, 60)))
    if kind == "update":
        return ("update", str(rng.choice(["City", "OS"])),
                int(rng.integers(0, 60)), int(rng.integers(0, 6)))
    return (kind,)


@pytest.mark.parametrize("case_seed", [0, 1, 2])
def test_seeded_random_sequences_match_oracle(case_seed):
    """Seeded slice of the randomized harness that runs WITHOUT hypothesis —
    the op distribution is the same one the hypothesis test draws from."""
    rng = np.random.default_rng(case_seed)
    mirror = MutationMirror(_mk_db(n0=2000))
    for _ in range(int(rng.integers(3, 7))):
        _apply_op(mirror, _random_op(rng))
        mirror.check()


def test_fixed_mutation_sequence_matches_oracle():
    """A fixed adversarial interleaving covering every op interaction:
    delete-then-append to the same stratum (inclusion freqs must keep
    counting dead rows), updates that create new dictionary values, a
    delete that empties a stratum, and interleaved compactions."""
    mirror = MutationMirror(_mk_db(n0=3000))
    db, tbl = mirror.db, mirror.db.tables["s"]
    cities = tbl.dictionaries["City"]
    q = Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2))
    db.query(q)   # stripe + compile NOW so every mutation (and the compact
    # ops) exercises the incremental device path, not a fresh stripe at the end
    seq = [
        ("delete", "City", 0),                   # hammer the top stratum
        ("append", 300, 123),                    # ...then refill it
        ("update", "City", 1, 1),                # move stratum 1 to upd1
        ("delete", "OS", 2),
        ("compact",),
        ("decay",),                              # forgive the churned strata
        ("update", "OS", 0, 2),                  # numeric assignment
        ("basecompact",),                        # drop the dead base rows
        ("append", 150, 456),
        ("delete", "City", 1),                   # stratum 1 now fully dead
        ("decay",),                              # ...decay empties its freq
        ("compact",),
        ("basecompact",),
    ]
    mirror.check()
    for op in seq:
        _apply_op(mirror, op)
        mirror.check()
    # the emptied stratum really is empty — live count 0, and the decay
    # after the delete forgave its dead inclusion weight entirely
    fam = db.families["s"][("City",)]
    c1 = int(np.nonzero((fam.strata_keys == tbl.encode_value(
        "City", cities[1])).all(axis=1))[0][0])
    assert fam.live_freqs[c1] == 0 and fam.stratum_freqs[c1] == 0
    # and the engine's device path agrees with the exact path afterwards
    q = Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2))
    got = {g.key: g.estimate for g in db.query(q).groups}
    exact = {g.key: g.estimate
             for g in db.exact_query(Query("s", AggOp.COUNT,
                                           group_by=("OS",))).groups}
    assert set(got) == set(exact)
    for key, est in got.items():
        assert abs(est - exact[key]) / max(exact[key], 1.0) < 0.25


def test_contained_stratum_stays_exact_through_mutations():
    """For a stratum fully contained in the sample (F < K₁), COUNT answers
    are EXACT before and after every mutation — the sharpest end-to-end
    check that tombstones hit precisely the right sampled rows."""
    db = _mk_db(n0=4000, k1=600.0)
    tbl = db.tables["s"]
    cities = tbl.dictionaries["City"]
    counts = np.bincount(tbl.host_column("City"), minlength=len(cities))
    code = int(np.argmin(np.where(counts > 0, counts, 1 << 30)))
    city = cities[code]
    q = Query("s", AggOp.COUNT,
              predicate=Predicate.where(Atom("City", CmpOp.EQ, city)))
    assert abs(db.query(q).groups[0].estimate - counts[code]) < 1e-3

    # delete half of that city's rows (those on os0)
    rep = db.delete_rows("s", Predicate.where(
        Atom("City", CmpOp.EQ, city), Atom("OS", CmpOp.EQ, "os0")))
    want = int(((tbl.host_column("City") == code) & tbl.live).sum())
    assert rep.mutation.n_tombstoned == counts[code] - want
    assert abs(db.query(q).groups[0].estimate - want) < 1e-3
    assert abs(db.exact_query(q).groups[0].estimate - want) < 1e-6

    # update the remainder away: the stratum vanishes from answers
    db.update_rows("s", Predicate.where(Atom("City", CmpOp.EQ, city)),
                   {"City": "cityELSEWHERE"})
    assert db.query(q).groups == []
    assert db.exact_query(q).groups == []
    q2 = Query("s", AggOp.COUNT, predicate=Predicate.where(
        Atom("City", CmpOp.EQ, "cityELSEWHERE")))
    assert abs(db.query(q2).groups[0].estimate - want) < 1e-3


# ------------------------------------------- ghost-fraction compaction

def test_tombstones_keep_programs_valid_and_compaction_reclaims():
    """Ghost-fraction stress (extends test_ingest's stale-program tests):
    drive a family past the compaction threshold with deletes; compiled
    programs must survive BOTH the tombstone scatters (shape class
    untouched) and the geometry-preserving compaction — and keep answering
    with post-mutation data."""
    db = _mk_db(n0=6000, k1=600.0)
    q = Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2))
    db.query(q)    # warm: stripe + AOT compile
    progs = dict(db._programs)
    assert progs
    shapes = {phi: db._striped[("s", phi)].shape_class
              for phi in db.families["s"]}

    for day in range(6):
        db.delete_rows("s", Predicate.where(Atom("dt", CmpOp.EQ, day)))
    # deletes landed on the warm striped blocks as ghosts
    fracs = db.ghost_fractions("s")
    assert fracs and all(f > 0 for f in fracs.values())
    assert all(db._programs.get(k) is v for k, v in progs.items()), \
        "tombstone scatter must not invalidate compiled programs"

    maint = SampleMaintainer(db, "s", [QueryTemplate(frozenset({"City"}), 1.0)],
                             MaintenanceConfig(compact_threshold=0.05))
    compacted = maint.compact()
    assert sorted(compacted) == sorted(db.families["s"]), compacted
    after = db.ghost_fractions("s")
    assert all(f <= 0.05 for f in after.values()), after
    # geometry pinned: same shape class, same compiled programs
    for phi, sc in shapes.items():
        assert db._striped[("s", phi)].shape_class == sc
    assert all(db._programs.get(k) is v for k, v in progs.items()), \
        "geometry-preserving compaction must keep compiled programs"
    # ...and those programs answer with the compacted, post-delete data
    got = {g.key: g.estimate for g in db.query(q).groups}
    exact = {g.key: g.estimate
             for g in db.exact_query(Query("s", AggOp.COUNT,
                                           group_by=("OS",))).groups}
    for key, est in got.items():
        assert abs(est - exact[key]) / max(exact[key], 1.0) < 0.25


def test_run_epoch_compacts_past_threshold():
    """The maintenance epoch itself fires the compaction policy (periodic
    restripe — not only on block growth)."""
    db = _mk_db(n0=5000, k1=500.0)
    db.query(Query("s", AggOp.COUNT, bound=ErrorBound(0.2)))   # stripe
    maint = SampleMaintainer(
        db, "s", [QueryTemplate(frozenset({"City"}), 1.0)],
        MaintenanceConfig(drift_threshold=0.9, compact_threshold=0.05))
    for day in range(5):
        db.delete_rows("s", Predicate.where(Atom("dt", CmpOp.EQ, day)))
    assert any(f > 0.05 for f in db.ghost_fractions("s").values())
    report = maint.run_epoch(delta=synth.sessions_table(100, seed=5,
                                                        n_cities=50))
    assert report["compacted"], report
    assert all(f <= 0.05 for f in db.ghost_fractions("s").values())


# --------------------------------- storage reclamation (base compact + decay)

def test_base_compaction_remaps_row_ids_for_every_family():
    """After Table.compact + BlinkDB.compact_table, EVERY family in play —
    stratified on one column, on two columns, and the uniform family — has
    its row_ids re-keyed so they address exactly the same rows in the
    compacted table, and the striped slot_row_ids mirrors agree."""
    db = _mk_db(n0=4000, k1=300.0)
    db.add_family("s", ("City", "OS"))
    tbl = db.tables["s"]
    q = Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2))
    db.query(q)   # stripe + compile every family's machinery
    for day in range(8):
        db.delete_rows("s", Predicate.where(Atom("dt", CmpOp.EQ, day)))
    before = {phi: {c: db.families["s"][phi].host_column(c).copy()
                    for c in tbl.schema.column_names}
              for phi in db.families["s"]}
    progs = dict(db._programs)
    comp = db.compact_table("s")
    assert comp is not None and comp.n_dropped > 0
    assert tbl.live is None and tbl.n_rows == comp.n_rows_after
    assert db.compact_table("s") is None   # idempotent: nothing left
    for phi, cols in before.items():
        fam = db.families["s"][phi]
        assert (fam.row_ids >= 0).all() and (fam.row_ids < tbl.n_rows).all()
        # same rows, new addresses: family columns still match the base rows
        for c, old in cols.items():
            np.testing.assert_array_equal(fam.host_column(c), old)
            np.testing.assert_array_equal(tbl.host_column(c)[fam.row_ids],
                                          fam.host_column(c))
        striped = db._striped.get(("s", phi))
        if striped is not None:
            ids = striped.slot_row_ids
            occ = ids[: striped.n_rows]
            assert (occ < tbl.n_rows).all()
            live_slots = occ >= 0
            # every occupied non-ghost slot names a real (remapped) row
            for c in ("City", "OS"):
                col = tbl.host_column(c)
                np.testing.assert_array_equal(
                    col[occ[live_slots]],
                    np.asarray(striped.columns[c]).T.reshape(-1)
                    [: striped.n_rows][live_slots])
    # zero device invalidation: every compiled program survived
    assert all(db._programs.get(k) is v for k, v in progs.items()), \
        "base compaction must not invalidate sampled-path programs"
    got = {g.key: g.estimate for g in db.query(q).groups}
    exact = {g.key: g.estimate
             for g in db.exact_query(Query("s", AggOp.COUNT,
                                           group_by=("OS",))).groups}
    for key, est in got.items():
        assert abs(est - exact[key]) / max(exact[key], 1.0) < 0.25


def test_base_compaction_then_mutations_stay_consistent():
    """The remapped ids keep working: deletes AFTER a base compaction must
    find their sampled copies (tombstones match on row ids), and appends
    land at the compacted end."""
    db = _mk_db(n0=3000, k1=600.0)
    tbl = db.tables["s"]
    cities = tbl.dictionaries["City"]
    counts = np.bincount(tbl.host_column("City"), minlength=len(cities))
    # largest stratum still CONTAINED in the sample (F < K₁): exact answers,
    # and populous enough that per-OS deletes never empty it
    code = int(np.argmax(np.where(counts < 500, counts, -1)))
    city = cities[code]
    q = Query("s", AggOp.COUNT,
              predicate=Predicate.where(Atom("City", CmpOp.EQ, city)))
    db.query(q)
    db.delete_rows("s", Predicate.where(Atom("OS", CmpOp.EQ, "os0")))
    assert db.compact_table("s") is not None
    # post-compaction delete of a CONTAINED stratum: exact before and after
    want = int((tbl.host_column("City") == code).sum())
    assert abs(db.query(q).groups[0].estimate - want) < 1e-3
    db.delete_rows("s", Predicate.where(Atom("City", CmpOp.EQ, city),
                                        Atom("OS", CmpOp.EQ, "os1")))
    want = int(((tbl.host_column("City") == code) & tbl.live).sum())
    assert abs(db.query(q).groups[0].estimate - want) < 1e-3
    assert abs(db.exact_query(q).groups[0].estimate - want) < 1e-6
    db.append_rows("s", synth.sessions_table(200, seed=42, n_cities=50))
    assert abs(db.exact_query(q).groups[0].estimate
               - db.query(q).groups[0].estimate) < 1e-3


def test_decay_restores_sample_utilization():
    """Churn thins a stratified family under monotone inclusion freqs; the
    decay epoch restores its sampled-row count toward the fresh-build level
    and keeps HT estimates exact for contained strata."""
    db = _mk_db(n0=6000, k1=400.0)
    tbl = db.tables["s"]
    q = Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2))
    db.query(q)
    # churn: delete half the days, refill with fresh rows, repeat
    for round_ in range(3):
        for day in range(0, 30, 2):
            db.delete_rows("s", Predicate.where(Atom("dt", CmpOp.EQ, day)))
        db.append_rows("s", synth.sessions_table(1500, seed=100 + round_,
                                                 n_cities=50))
    fam = db.families["s"][("City",)]
    thinned = fam.n_rows
    assert (fam.stratum_freqs.sum() > 1.5 * fam.live_freqs.sum()), \
        "churn setup should inflate cumulative freqs"
    from repro.core.maintenance import strata_to_decay
    strata = strata_to_decay(fam, 1.5)
    assert strata.size > 0
    block = db.decay_family("s", ("City",), strata)
    fam2 = db.families["s"][("City",)]
    assert block.n_admitted > 0 and fam2.n_rows > thinned, \
        (thinned, fam2.n_rows)
    np.testing.assert_array_equal(fam2.stratum_freqs[strata],
                                  fam2.live_freqs[strata])
    # rates exact by construction: a contained stratum answers exactly
    counts = np.bincount(tbl.host_column("City")[np.asarray(tbl.live)]
                         if tbl.live is not None
                         else tbl.host_column("City"))
    code = int(np.argmin(np.where(counts > 0, counts, 1 << 30)))
    city = tbl.dictionaries["City"][code]
    qc = Query("s", AggOp.COUNT,
               predicate=Predicate.where(Atom("City", CmpOp.EQ, city)))
    got = db.query(qc).groups[0].estimate
    exact = db.exact_query(qc).groups[0].estimate
    assert abs(got - exact) < 1e-3, (got, exact)


def test_run_epoch_runs_reclamation():
    """The maintenance epoch drives both reclamation passes from its config
    knobs: past base_compact_threshold the base table physically shrinks,
    and over-ratio strata decay — all inside one run_epoch(delta=...)."""
    db = _mk_db(n0=5000, k1=400.0)
    db.query(Query("s", AggOp.COUNT, bound=ErrorBound(0.2)))   # stripe
    maint = SampleMaintainer(
        db, "s", [QueryTemplate(frozenset({"City"}), 1.0)],
        MaintenanceConfig(drift_threshold=0.9, compact_threshold=0.05,
                          base_compact_threshold=0.1, decay_ratio=1.2))
    for day in range(10):
        db.delete_rows("s", Predicate.where(Atom("dt", CmpOp.EQ, day)))
    tbl = db.tables["s"]
    assert db.dead_fraction("s") > 0.1
    n_phys_before = tbl.n_rows
    report = maint.run_epoch(delta=synth.sessions_table(100, seed=5,
                                                        n_cities=50))
    assert report["base_compacted"] > 0
    assert tbl.n_rows < n_phys_before
    assert tbl.live is None   # compaction must clear the tombstone mask
    assert report["decayed"].get(("City",)), report
    fam = db.families["s"][("City",)]
    assert fam.stratum_freqs.sum() <= 1.2 * fam.live_freqs.sum() + 1e-9
    # steady state: an immediate second epoch has nothing left to reclaim
    report2 = maint.run_epoch(delta=synth.sessions_table(50, seed=6,
                                                         n_cities=50))
    assert report2["base_compacted"] == 0 and not report2["decayed"]


# ------------------------------------------------------- drift (satellite)

def test_check_drift_accounts_for_tombstoned_rows():
    """A delete-heavy epoch must not mask drift: if deletes removed the top
    city and a replacement table restores it, the pre-fix comparison (stale
    freqs still counting the dead rows vs the new histogram) reports ~zero
    drift; the live-aligned comparison reports the real shift."""
    raw = synth.sessions_table(8000, seed=3, n_cities=30, city_s=1.5)
    tbl = table_lib.from_columns("s", raw)
    db = BlinkDB(EngineConfig(k1=400.0, m=3, seed=2))
    db.register_table("s", tbl)
    db.add_family("s", ("City",))
    maint = SampleMaintainer(db, "s",
                             [QueryTemplate(frozenset({"City"}), 1.0)],
                             MaintenanceConfig(drift_threshold=0.05))
    fam = db.families["s"][("City",)]
    stale_before = fam.stratum_freqs.copy()

    # delete-heavy epoch: wipe out the (Zipf-top) city
    top = tbl.dictionaries["City"][
        np.argmax(np.bincount(tbl.host_column("City")))]
    db.delete_rows("s", Predicate.where(Atom("City", CmpOp.EQ, top)))
    fam = db.families["s"][("City",)]
    # inclusion freqs still count the dead rows; live freqs don't
    np.testing.assert_array_equal(fam.stratum_freqs, stale_before)
    assert fam.live_freqs.sum() < stale_before.sum()

    # a replacement table where the top city is back at full strength
    drift = maint.check_drift(table_lib.from_columns("s", raw))
    assert drift[("City",)] > 0.05, (
        "live-aligned drift must see the delete-heavy shift; the stale "
        f"inclusion histogram would report ~0, got {drift}")
    # while a replacement matching the post-delete reality reports ~none
    live_raw = {k: v[np.asarray(tbl.live)] for k, v in raw.items()}
    drift2 = maint.check_drift(table_lib.from_columns("s", live_raw))
    assert drift2[("City",)] < 0.01, drift2


def test_check_drift_respects_new_table_tombstones():
    """The new table's own tombstones are excluded from its histogram."""
    raw = synth.sessions_table(5000, seed=4, n_cities=20)
    tbl = table_lib.from_columns("s", raw)
    db = BlinkDB(EngineConfig(k1=400.0, m=3, seed=2))
    db.register_table("s", tbl)
    db.add_family("s", ("City",))
    maint = SampleMaintainer(db, "s",
                             [QueryTemplate(frozenset({"City"}), 1.0)])
    new_tbl = table_lib.from_columns("s", raw)
    top = tbl.dictionaries["City"][
        np.argmax(np.bincount(tbl.host_column("City")))]
    new_tbl.delete(Predicate.where(Atom("City", CmpOp.EQ, top)))
    drift = maint.check_drift(new_tbl)
    assert drift[("City",)] > 0.05, drift


def test_family_built_on_tombstoned_table_appends_consistently():
    """A family built AFTER deletes has a LIVE inclusion base; a later
    append must extend that base (not the table's physical count), keeping
    the uniform family's per-row rate pinned at exactly p and contained
    strata exact."""
    tbl = table_lib.from_columns("s", synth.sessions_table(4000, seed=7,
                                                           n_cities=50))
    db = BlinkDB(EngineConfig(k1=300.0, m=3, seed=SEED))
    db.register_table("s", tbl)
    db.delete_rows("s", Predicate.where(Atom("OS", CmpOp.EQ, "os0")))
    db.add_family("s", ("City",))     # built on the tombstoned table
    db.add_family("s", ())
    unif = db.families["s"][()]
    assert unif.stratum_freqs[0] == tbl.n_live   # live inclusion base
    p = unif.ks[0] / unif.stratum_freqs[0]
    db.query(Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2)))
    db.append_rows("s", synth.sessions_table(500, seed=13, n_cities=50))
    unif = db.families["s"][()]
    assert abs(unif.ks[0] / unif.stratum_freqs[0] - p) < 1e-9, \
        "uniform rate must stay exactly p across the append"
    # contained strata stay exact through the whole flow
    cities = tbl.dictionaries["City"]
    counts = np.bincount(tbl.host_column("City")[np.asarray(tbl.live)],
                         minlength=len(cities))
    code = int(np.argmin(np.where(counts > 0, counts, 1 << 30)))
    q = Query("s", AggOp.COUNT,
              predicate=Predicate.where(Atom("City", CmpOp.EQ, cities[code])))
    got = db.query(q).groups[0].estimate
    exact = db.exact_query(q).groups[0].estimate
    assert abs(got - exact) < 1e-3, (got, exact)


def test_noop_update_invalidates_nothing():
    """An update whose predicate matches no live rows must not drop striped
    blocks, compiled programs, or fk state — retried/raced mutations are
    common under churn and must stay free."""
    db = _mk_db(n0=2000)
    q = Query("s", AggOp.COUNT, group_by=("OS",), bound=ErrorBound(0.2))
    db.query(q)   # warm
    progs = dict(db._programs)
    striped = dict(db._striped)
    rep = db.update_rows("s", Predicate.where(Atom("City", CmpOp.EQ, "nope")),
                         {"Bitrate": 1.0})
    assert rep.mutation.n_tombstoned == 0 and rep.epoch is None
    assert db._programs == progs
    assert all(db._striped.get(k) is v for k, v in striped.items())


def test_dimension_mutations_refresh_joins():
    """Mutating a DIMENSION table must flow through to fact joins: an
    updated dim row's new version wins over its tombstoned original, and a
    deleted dim row's keys dangle to the sentinel instead of serving stale
    attributes."""
    from repro.core.joins import Join
    fact = table_lib.from_columns("fact", {
        "UserId": np.array(["u0", "u1", "u2"] * 100),
        "x": np.ones(300, np.float32)})
    dim = table_lib.from_columns("users", {
        "UserId": np.array(["u0", "u1", "u2"]),
        "Country": np.array(["US", "US", "DE"])})
    db = BlinkDB(EngineConfig(k1=500.0, m=2))
    db.register_table("fact", fact)
    db.register_table("users", dim)
    db.add_family("fact", ("UserId",))
    db.add_family("fact", ())
    q = Query("fact", AggOp.COUNT, group_by=("users.Country",),
              joins=(Join("users", "UserId", "UserId"),))
    assert {g.key: g.estimate for g in db.exact_query(q).groups} == \
        {("US",): 200.0, ("DE",): 100.0}   # warm fk map + gathers

    # update: u1 moves US -> FR; the re-inserted live version must win
    db.update_rows("users", Predicate.where(Atom("UserId", CmpOp.EQ, "u1")),
                   {"Country": "FR"})
    want = {("US",): 100.0, ("DE",): 100.0, ("FR",): 100.0}
    assert {g.key: g.estimate for g in db.exact_query(q).groups} == want
    assert {g.key: g.estimate for g in db.query(q).groups} == want

    # delete: u2's rows must dangle (sentinel), not serve "DE"
    db.delete_rows("users", Predicate.where(Atom("UserId", CmpOp.EQ, "u2")))
    want = {("US",): 100.0, ("FR",): 100.0}
    got = {g.key: g.estimate for g in db.exact_query(q).groups}
    assert all(got.get(k) == v for k, v in want.items()) and \
        ("DE",) not in got, got


# ------------------------------------------------------------- exact path

def test_exact_query_excludes_tombstones_and_keeps_programs():
    """Deletes leave the physical table length unchanged, so exact-path
    programs survive — the live mask rides as a traced argument."""
    db = _mk_db(n0=3000)
    q = Query("s", AggOp.COUNT, group_by=("OS",))
    before = {g.key: g.estimate for g in db.exact_query(q).groups}
    progs = dict(db._exact_programs)
    db.delete_rows("s", Predicate.where(Atom("OS", CmpOp.EQ, "os0")))
    assert all(db._exact_programs.get(k) is v for k, v in progs.items()), \
        "delete must not retire exact programs (length unchanged)"
    after = {g.key: g.estimate for g in db.exact_query(q).groups}
    assert ("os0",) in before and ("os0",) not in after
    for key in after:
        assert after[key] == before[key]
    ans = db.exact_query(q)
    assert ans.rows_total == db.tables["s"].n_live


# ------------------------------------- the kernel's row grid after mutations

@pytest.mark.parametrize("mutation", ["tombstone", "append"])
def test_bounded_scan_after_mutation_equals_fresh_restripe(mutation):
    """The fused kernel visits only the row blocks a small K needs. After a
    tombstone (dead slots stay in the block, so the live rows below K reach
    further than the family's live count) and after an append into the
    block's headroom (new slots sit past the key-ordered head, out of key
    order), a scan at the family's smallest K must return what it returns
    on a freshly restriped block: the same rows (counts equal exactly) and
    the same sums. A bound taken from the family's live-row count, or one
    that skips the appended tail, misses rows in each case."""
    tbl = table_lib.from_columns("s", synth.sessions_table(40_000, seed=5))
    db = BlinkDB(EngineConfig(k1=1000.0, m=3, seed=1, use_pallas=True))
    db.register_table("s", tbl)
    db.add_family("s", ())
    q = Query("s", AggOp.SUM, "SessionTime", group_by=("OS",))
    db._run_at_k("s", q, (), db.families["s"][()].ks[0])   # stripe, compile
    if mutation == "tombstone":
        for day in range(15):
            db.delete_rows("s", Predicate.where(Atom("dt", CmpOp.EQ, day)))
        striped = db._striped[("s", ())]
        k = min(db.families["s"][()].ks)
        dead = striped.n_ghosts
        assert dead > 5000 and striped.n_sorted == striped.n_rows
    else:
        assert not db.append_rows("s", synth.sessions_table(
            4000, seed=6)).restriped
        striped = db._striped[("s", ())]
        k = min(db.families["s"][()].ks)
        tail = db.families["s"][()].entry_key_host.size - striped.n_sorted
        assert striped.n_sorted < striped.n_rows and tail > 1000
    fam = db.families["s"][()]
    spans = exec_lib.row_spans(striped, k)
    # the bound is a small share of the block, and more than the live count
    assert exec_lib.rows_visited(striped, spans, True) < striped.n_rows // 2
    assert spans[0] > fam.prefix_for_k(k) or spans[2] > spans[1]
    got, rows, _, _ = db._run_at_k("s", q, (), k)
    assert db.compact_family("s", ())
    want, rows_fresh, _, _ = db._run_at_k("s", q, (), k)
    assert rows == rows_fresh == fam.prefix_for_k(k)
    np.testing.assert_array_equal(np.asarray(got.n), np.asarray(want.n))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
