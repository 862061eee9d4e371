"""Incremental ingestion + delta-based sample maintenance (§3.2.3/§4.5).

The load-bearing property: after ANY sequence of appends, the incrementally
merged family is BIT-IDENTICAL to a from-scratch rebuild fed the same
per-row units (the host oracle) — nested prefixes, exact HT rates, identical
query estimates. Plus cache-validity: appends must never be answered by a
stale compiled program.
"""
import numpy as np
import pytest

from repro.core import (AggOp, Atom, BlinkDB, CmpOp, Conjunction, EngineConfig,
                        ErrorBound, Predicate, Query, QueryTemplate)
from repro.core import sampling as samp
from repro.core import table as table_lib
from repro.core.engine import _union_answers
from repro.core.maintenance import MaintenanceConfig, SampleMaintainer
from repro.core.types import GroupResult
from repro.data import synth


# ------------------------------------------------------------- table layer

_ENCODE_CASES = {
    "mixed_len_unicode": np.array(["b", "a", "ab", "", "abc", "é", "a", "zz"]),
    "empty_str": np.array([], dtype="<U3"),
    "constant_str": np.array(["x", "x"]),
    "negative_ints": np.random.default_rng(1).integers(-5, 5, 100),
    "wide_ints": np.random.default_rng(2).integers(0, 10**12, 100),
    "int8": np.random.default_rng(3).integers(0, 7, 1000).astype(np.int8),
    "bools": np.array([True, False, True]),
    "labels": synth.sessions_table(20_000, seed=3)["City"],
}


@pytest.mark.parametrize("case", sorted(_ENCODE_CASES))
def test_dictionary_encoding_matches_np_unique(case):
    """from_columns' sort-free encoder numbers values exactly as
    np.unique(return_inverse=True) does — dictionary order, dtype, codes."""
    values = _ENCODE_CASES[case]
    want_dict, want_codes = np.unique(values, return_inverse=True)
    got_dict, got_codes = table_lib._unique_inverse(values)
    assert got_dict.dtype == want_dict.dtype
    assert got_dict.tolist() == want_dict.tolist()
    np.testing.assert_array_equal(got_codes, want_codes)


@pytest.mark.parametrize("phi", [("City",), ("OS", "URL"),
                                 ("City", "URL", "dt"), ("Bitrate",)])
def test_combined_codes_match_np_unique_rows(phi):
    tbl = table_lib.from_columns("s", synth.sessions_table(30_000, seed=2))
    mats = np.stack([tbl.host_column(c) for c in sorted(phi)], axis=1)
    want_rows, want_ids = np.unique(mats, axis=0, return_inverse=True)
    got_ids, got_rows = table_lib.combined_codes(tbl, phi)
    np.testing.assert_array_equal(got_ids, want_ids.astype(np.int64))
    np.testing.assert_array_equal(got_rows, want_rows.astype(np.int32))
    assert got_ids.dtype == np.int64 and got_rows.dtype == np.int32


def test_append_extends_dictionaries_without_recoding():
    tbl = table_lib.from_columns("t", {
        "key": np.array(["b", "a", "b"]), "x": np.array([1., 2., 3.],
                                                        np.float32)})
    old_codes = np.asarray(tbl.columns["key"]).copy()
    old_dict = tbl.dictionaries["key"].copy()
    delta = tbl.append({"key": np.array(["c", "a"]),
                        "x": np.array([4., 5.], np.float32)})
    # existing rows keep their codes; the dictionary only grows at the tail
    # (tbl.columns refreshes its lazily-stale device copy on access)
    np.testing.assert_array_equal(np.asarray(tbl.columns["key"]),
                                  np.concatenate([old_codes, [2, 0]]))
    np.testing.assert_array_equal(tbl.dictionaries["key"][:2], old_dict)
    assert list(delta.new_dict_values["key"]) == ["c"]
    assert tbl.cardinality("key") == 3
    assert tbl.n_rows == 5
    assert tbl.encode_value("key", "c") == 2
    assert delta.start_row == 3 and delta.n_rows == 2
    np.testing.assert_array_equal(delta.columns["key"], [2, 0])


def test_append_rejects_schema_mismatch_without_partial_mutation():
    tbl = table_lib.from_columns("t", {"key": np.array(["a"]),
                                       "x": np.array([1.], np.float32)})
    with pytest.raises(ValueError, match="delta columns"):
        tbl.append({"key": np.array(["a"])})
    # ragged delta with a NEW categorical value: the rejection must not
    # leave a phantom dictionary entry / inflated cardinality behind
    with pytest.raises(ValueError, match="length"):
        tbl.append({"key": np.array(["a", "b"]),
                    "x": np.array([1.], np.float32)})
    # a measure that cannot cast to f32 must also reject atomically
    with pytest.raises(ValueError):
        tbl.append({"key": np.array(["b"]), "x": np.array(["oops"])})
    assert tbl.n_rows == 1
    assert tbl.cardinality("key") == 1
    np.testing.assert_array_equal(tbl.dictionaries["key"], ["a"])


def test_map_codes_stable_preserves_ids_and_extends():
    keys = np.array([[0, 1], [2, 0]], np.int32)
    mat = np.array([[2, 0], [3, 3], [0, 1], [3, 3]], np.int32)
    codes, new_keys = table_lib.map_codes_stable(mat, keys)
    np.testing.assert_array_equal(codes, [1, 2, 0, 2])
    np.testing.assert_array_equal(new_keys[:2], keys)
    np.testing.assert_array_equal(new_keys[2], [3, 3])
    freqs = table_lib.extend_frequencies(np.array([10, 20]), codes, 3)
    np.testing.assert_array_equal(freqs, [11, 21, 2])


# --------------------------------------------- merge == from-scratch oracle

def _random_appends(base_n, n_appends, rng, **kw):
    raws = [synth.sessions_table(base_n, seed=int(rng.integers(1e6)), **kw)]
    for _ in range(n_appends):
        d = int(rng.integers(200, 2500))
        raws.append(synth.sessions_table(d, seed=int(rng.integers(1e6)),
                                         **kw))
    return raws


def _assert_families_identical(fam, oracle):
    """Exact equality up to entry-key TIES: the merged family and the oracle
    contain the same rows with the same keys/rates, but exact f32 entry-key
    collisions (likely at 1e4+ rows) may order differently under the two
    stable sorts. Queries are order-invariant within a prefix, so compare
    under a tie-canonical permutation (lexsort by unit within key)."""
    assert fam.n_rows == oracle.n_rows
    assert fam.prefix_sizes == oracle.prefix_sizes
    np.testing.assert_array_equal(fam.entry_key_host, oracle.entry_key_host)

    from test_mutations import _canon   # (ek, row_id): one shared total order
    pa, pb = _canon(fam), _canon(oracle)
    np.testing.assert_array_equal(np.asarray(fam.freq)[pa],
                                  np.asarray(oracle.freq)[pb])
    np.testing.assert_array_equal(np.asarray(fam.unit)[pa],
                                  np.asarray(oracle.unit)[pb])
    np.testing.assert_array_equal(fam.row_ids[pa], oracle.row_ids[pb])
    for c in fam.columns:
        np.testing.assert_array_equal(np.asarray(fam.columns[c])[pa],
                                      np.asarray(oracle.columns[c])[pb])
    np.testing.assert_array_equal(np.sort(fam.stratum_freqs),
                                  np.sort(oracle.stratum_freqs))
    # append-only: live counts never diverge from the inclusion freqs
    np.testing.assert_array_equal(fam.live_freqs, fam.stratum_freqs)
    np.testing.assert_array_equal(np.sort(fam.live_freqs),
                                  np.sort(oracle.live_freqs))


@pytest.mark.parametrize("case_seed", [0, 1, 2])
def test_merged_family_matches_oracle(case_seed):
    """Property test: after N random appends (including ones that introduce
    new strata), the merged family equals build_family on the appended table
    with the concatenated unit segments — exactly, not approximately."""
    rng = np.random.default_rng(case_seed)
    raws = _random_appends(12_000, 3, rng, n_cities=180 + 30 * case_seed)
    seed = 40 + case_seed
    tbl = table_lib.from_columns("s", raws[0])
    fam = samp.build_family(tbl, ("City", "OS"), k1=300.0, m=3, seed=seed)
    units = [samp.base_units(tbl.n_rows, seed)]
    for epoch, raw in enumerate(raws[1:], start=1):
        delta = tbl.append(raw)
        du = samp.delta_units(delta.n_rows, seed, epoch)
        units.append(du)
        fam, block = samp.merge_family(fam, delta.columns, du)
        assert block.n_rows <= delta.n_rows
    oracle = samp.build_family(tbl, ("City", "OS"), k1=300.0, m=3,
                               units=np.concatenate(units))
    _assert_families_identical(fam, oracle)


def test_merged_uniform_family_matches_oracle():
    rng = np.random.default_rng(7)
    raws = _random_appends(10_000, 3, rng)
    seed, frac = 9, 0.3
    tbl = table_lib.from_columns("s", raws[0])
    fam = samp.build_uniform_family(tbl, frac, m=3, seed=seed)
    units = [samp.base_units(tbl.n_rows, seed, uniform=True)]
    for epoch, raw in enumerate(raws[1:], start=1):
        delta = tbl.append(raw)
        du = samp.delta_units(delta.n_rows, seed, epoch, uniform=True)
        units.append(du)
        fam, _ = samp.merge_family(fam, delta.columns, du,
                                   new_k1=frac * tbl.n_rows)
    oracle = samp.build_uniform_family(tbl, frac, m=3,
                                       units=np.concatenate(units))
    _assert_families_identical(fam, oracle)
    np.testing.assert_allclose(fam.ks, oracle.ks, rtol=1e-12)


def test_merged_family_invariants_and_exact_ht_rates():
    """Nesting, sortedness and EXACT Horvitz–Thompson rates after merges:
    rate(row, K) must equal min(1, K / F_new) with F_new the recounted
    full-table stratum frequency."""
    tbl = table_lib.from_columns("s", synth.sessions_table(15_000, seed=3))
    fam = samp.build_family(tbl, ("City",), k1=250.0, m=3, seed=5)
    for epoch in (1, 2):
        delta = tbl.append(synth.sessions_table(2_000, seed=50 + epoch))
        fam, _ = samp.merge_family(
            fam, delta.columns, samp.delta_units(delta.n_rows, 5, epoch))
    ek = fam.entry_key_host
    assert np.all(np.diff(ek) >= 0)
    assert fam.prefix_sizes[0] == fam.n_rows
    assert list(fam.prefix_sizes) == sorted(fam.prefix_sizes, reverse=True)
    for k, n in zip(fam.ks, fam.prefix_sizes):
        assert np.all(ek[:n] < k)
        if n < fam.n_rows:
            assert ek[n] >= k
    # freq column must match a full recount of the appended table
    codes, _ = table_lib.combined_codes(tbl, ("City",))
    full = table_lib.stratum_frequencies(codes, int(codes.max()) + 1)
    city = np.asarray(fam.columns["City"])
    np.testing.assert_array_equal(np.asarray(fam.freq),
                                  full[city].astype(np.float32))
    for k in fam.ks:
        np.testing.assert_allclose(np.asarray(fam.rate(k)),
                                   np.minimum(1.0, k / full[city]), rtol=1e-6)


# ---------------------------------------------------------- engine parity

def _engine_with_family(tbl, seed=3):
    db = BlinkDB(EngineConfig(k1=600.0, m=3, seed=seed))
    db.register_table("s", tbl)
    db.add_family("s", ("City",))
    db.add_family("s", ())
    return db


def test_append_rows_matches_oracle_engine():
    """Acceptance: queries after BlinkDB.append_rows answer identically
    (within fp tolerance) to an engine whose families were rebuilt from
    scratch on the appended table (same unit segments)."""
    seed = 3
    tbl = table_lib.from_columns("s", synth.sessions_table(25_000, seed=11))
    db = _engine_with_family(tbl, seed)
    frac = db.config.uniform_fraction
    units = [samp.base_units(tbl.n_rows, seed)]
    uunits = [samp.base_units(tbl.n_rows, seed, uniform=True)]
    for epoch in (1, 2):
        raw = synth.sessions_table(1_200 * epoch, seed=70 + epoch)
        db.append_rows("s", raw)
        d = len(raw["City"])
        units.append(samp.delta_units(d, seed, epoch))
        uunits.append(samp.delta_units(d, seed, epoch, uniform=True))

    # Oracle engine: same (appended) table object, families rebuilt from
    # scratch with the concatenated unit segments.
    db2 = BlinkDB(EngineConfig(k1=600.0, m=3, seed=seed))
    db2.register_table("s", db.tables["s"])
    db2.families["s"][("City",)] = samp.build_family(
        db.tables["s"], ("City",), 600.0, m=3, units=np.concatenate(units))
    db2.families["s"][()] = samp.build_uniform_family(
        db.tables["s"], frac, m=3, units=np.concatenate(uunits))

    cities = db.tables["s"].dictionaries["City"]
    queries = [
        Query("s", AggOp.COUNT,
              predicate=Predicate.where(Atom("City", CmpOp.EQ, cities[1])),
              bound=ErrorBound(0.1)),
        Query("s", AggOp.AVG, "SessionTime", group_by=("OS",),
              bound=ErrorBound(0.1)),
        Query("s", AggOp.SUM, "Bitrate",
              predicate=Predicate.where(Atom("City", CmpOp.EQ, cities[0]))),
        Query("s", AggOp.QUANTILE, "SessionTime", quantile=0.5,
              bound=ErrorBound(0.1)),
    ]
    for q in queries:
        a, b = db.query(q), db2.query(q)
        assert a.sample_phi == b.sample_phi
        ka = {g.key: g for g in a.groups}
        kb = {g.key: g for g in b.groups}
        assert ka.keys() == kb.keys()
        for key in ka:
            np.testing.assert_allclose(ka[key].estimate, kb[key].estimate,
                                       rtol=1e-5)
            np.testing.assert_allclose(ka[key].stderr, kb[key].stderr,
                                       rtol=1e-4, atol=1e-9)


def test_append_not_answered_by_stale_programs():
    """Cache validity: a warm compiled program must see appended rows.
    A stratum kept entirely (F < K) answers COUNT exactly, so the estimate
    after the append must equal the NEW exact count — a stale program would
    return the old one."""
    tbl = table_lib.from_columns("s", synth.sessions_table(20_000, seed=2))
    db = _engine_with_family(tbl)
    cities = db.tables["s"].dictionaries["City"]
    # find a city with a small stratum (fully contained: F << k1=600)
    counts = np.bincount(np.asarray(tbl.columns["City"]),
                         minlength=len(cities))
    code = int(np.argmin(np.where(counts > 0, counts, 1 << 30)))
    city = cities[code]
    q = Query("s", AggOp.COUNT,
              predicate=Predicate.where(Atom("City", CmpOp.EQ, city)),
              bound=ErrorBound(0.1))
    a1 = db.query(q)
    assert abs(a1.groups[0].estimate - counts[code]) < 1e-3
    progs = dict(db._programs)

    # append 50 more rows of exactly that city
    raw = synth.sessions_table(50, seed=9)
    raw["City"] = np.full(50, city, dtype=raw["City"].dtype)
    db.append_rows("s", raw)
    # same compiled programs survive the in-place merge...
    assert all(db._programs.get(k) is v for k, v in progs.items())
    # ...and answer with the appended data, not the stale prefix
    a2 = db.query(q)
    assert abs(a2.groups[0].estimate - (counts[code] + 50)) < 1e-3
    exact = db.exact_query(q)
    assert abs(exact.groups[0].estimate - (counts[code] + 50)) < 1e-6


def test_append_outgrowing_padding_restripes_and_stays_correct():
    """A delta larger than the stripe headroom forces a compacting restripe
    (programs recompile) — answers must stay exact for contained strata."""
    tbl = table_lib.from_columns("s", synth.sessions_table(8_000, seed=4))
    db = _engine_with_family(tbl)
    q = Query("s", AggOp.COUNT, bound=ErrorBound(0.2))
    db.query(q)  # warm + stripe
    report = db.append_rows("s", synth.sessions_table(6_000, seed=5))
    assert ("City",) in report.restriped or () in report.restriped
    got = db.query(Query("s", AggOp.COUNT, group_by=("OS",),
                         bound=ErrorBound(0.2)))
    exact = db.exact_query(Query("s", AggOp.COUNT, group_by=("OS",)))
    ex = {g.key: g.estimate for g in exact.groups}
    for g in got.groups:
        assert abs(g.estimate - ex[g.key]) / ex[g.key] < 0.2


def test_append_new_dictionary_value_is_queryable():
    tbl = table_lib.from_columns("s", synth.sessions_table(10_000, seed=6))
    db = _engine_with_family(tbl)
    db.query(Query("s", AggOp.COUNT, group_by=("City",),
                   bound=ErrorBound(0.2)))  # warm with the OLD cardinality
    raw = synth.sessions_table(300, seed=8)
    raw["City"] = np.array(["cityNEW"] * 300, dtype=raw["City"].dtype)
    db.append_rows("s", raw)
    # no bound -> largest K -> the 300-row stratum (< k1) is fully contained
    q = Query("s", AggOp.COUNT,
              predicate=Predicate.where(Atom("City", CmpOp.EQ, "cityNEW")))
    ans = db.query(q)
    assert abs(ans.groups[0].estimate - 300) < 1e-3
    # and the new value shows up as a GROUP BY key
    grouped = db.query(Query("s", AggOp.COUNT, group_by=("City",),
                             bound=ErrorBound(0.2)))
    assert ("cityNEW",) in {g.key for g in grouped.groups}


def test_public_append_strips_gathered_join_columns():
    """tbl.append on the PUBLIC table API must drop gathered "dim.col"
    columns — leaving them at the old length corrupts the exact/join path."""
    import jax.numpy as jnp
    tbl = table_lib.from_columns("t", {"key": np.array(["a", "b"]),
                                       "x": np.array([1., 2.], np.float32)})
    tbl.columns["dim.col"] = jnp.zeros(2, jnp.float32)
    tbl.append({"key": np.array(["a"]), "x": np.array([3.], np.float32)})
    assert "dim.col" not in tbl.columns
    assert all(len(np.asarray(tbl.columns[c])) == 3 for c in ("key", "x"))


def test_replacement_with_recoded_dictionary_rebuilds_families():
    """A replacement table whose dictionary gained a value that sorts FIRST
    shifts every code; surviving families hold old codes and MUST rebuild
    even though the distribution (and hence drift) is unchanged."""
    raw = synth.sessions_table(12_000, seed=4)
    tbl = table_lib.from_columns("s", raw)
    db = BlinkDB(EngineConfig(k1=400.0, m=3, seed=2))
    db.register_table("s", tbl)
    templates = [QueryTemplate(frozenset({"City"}), 1.0)]
    db.build_samples("s", templates, storage_budget_fraction=0.5)
    db.add_family("s", ("City",))
    maint = SampleMaintainer(db, "s", templates,
                             MaintenanceConfig(drift_threshold=0.05))
    extra = {k: v[:20] for k, v in synth.sessions_table(100, seed=5).items()}
    extra["City"] = np.full(20, "aaa")   # sorts before every "cityNNN"
    raw2 = {k: np.concatenate([raw[k], extra[k]]) for k in raw}
    tbl2 = table_lib.from_columns("s", raw2)
    report = maint.run_epoch(new_table=tbl2)
    # EVERY family that survived selection must have been rebuilt (despite
    # ~zero drift): surviving rows are coded under the replaced dictionary.
    assert sorted(report["rebuilt"]) == sorted(db.families["s"]), report
    city = raw["City"][0]   # the Zipf-top city: a one-code shift would
    q = Query("s", AggOp.COUNT,          # return its much smaller neighbour
              predicate=Predicate.where(Atom("City", CmpOp.EQ, city)))
    got = db.query(q).groups[0].estimate
    exact = db.exact_query(q).groups[0].estimate
    assert abs(got - exact) <= max(20.0, 0.15 * exact), (got, exact)


def test_run_epoch_delta_merges_or_rebuilds_on_drift():
    tbl = table_lib.from_columns("s", synth.sessions_table(15_000, seed=1,
                                                           city_s=1.4))
    db = BlinkDB(EngineConfig(k1=400.0, m=3, seed=2))
    db.register_table("s", tbl)
    templates = [QueryTemplate(frozenset({"City"}), 1.0)]
    db.build_samples("s", templates, storage_budget_fraction=0.5)
    db.add_family("s", ("City",))
    maint = SampleMaintainer(db, "s", templates,
                             MaintenanceConfig(drift_threshold=0.05))
    seed_before = db.config.seed
    low = maint.run_epoch(delta=synth.sessions_table(800, seed=21,
                                                     city_s=1.4))
    assert low["rebuilt"] == [] and low["objective"] is None
    assert ("City",) in low["merged"]
    high = maint.run_epoch(delta=synth.sessions_table(15_000, seed=22,
                                                      city_s=0.2))
    assert high["drift"][("City",)] > 0.05
    assert ("City",) in high["rebuilt"]
    assert db.config.seed == seed_before, \
        "run_epoch must not mutate the shared EngineConfig.seed"
    ans = db.query(Query("s", AggOp.COUNT, group_by=("OS",),
                         bound=ErrorBound(0.2)))
    assert ans.groups


def test_append_with_new_fk_value_joins_correctly():
    """A fact append whose delta introduces a NEW foreign-key value must
    refresh the cached fk→dim-row map — a stale map (sized by the old fk
    dictionary) would clamp-join the new rows to an arbitrary dim row."""
    from repro.core.joins import Join
    fact = table_lib.from_columns("fact", {
        "UserId": np.array(["u0", "u1", "u2"] * 100),
        "x": np.ones(300, np.float32)})
    dim = table_lib.from_columns("users", {
        "UserId": np.array(["u0", "u1", "u2", "u9"]),
        "Country": np.array(["US", "US", "DE", "FR"])})
    db = BlinkDB(EngineConfig(k1=500.0, m=2))
    db.register_table("fact", fact)
    db.register_table("users", dim)
    db.add_family("fact", ("UserId",))
    db.add_family("fact", ())
    q = Query("fact", AggOp.COUNT, group_by=("users.Country",),
              joins=(Join("users", "UserId", "UserId"),))
    ex1 = {g.key: g.estimate for g in db.exact_query(q).groups}
    assert ex1 == {("US",): 200.0, ("DE",): 100.0}  # warms the fk map
    db.append_rows("fact", {"UserId": np.array(["u9"] * 50),
                            "x": np.zeros(50, np.float32)})
    ex2 = {g.key: g.estimate for g in db.exact_query(q).groups}
    assert ex2 == {("US",): 200.0, ("DE",): 100.0, ("FR",): 50.0}
    # sampled path: every stratum is below k1 -> exact counts
    ans = {g.key: g.estimate for g in db.query(q).groups}
    assert ans == ex2


# ------------------------------------------------------- satellite fixes

def test_union_answers_copies_singleton_groups():
    g = GroupResult(("a",), 10.0, 2.0, 1.0, 2.0, 5.0, False)
    from repro.core.types import Answer
    q = Query("t", AggOp.SUM, "x")
    a = Answer(q, [g], ("x",), 1.0, 10, 100, 0.0, 0.95)
    out = _union_answers(q, [a])
    assert out.groups[0] is not g, "singleton group must be copied"
    assert (g.ci_low, g.ci_high) == (1.0, 2.0), \
        "sub-answer GroupResult mutated in place"
    assert out.groups[0].ci_low != 1.0  # recomputed from stderr


def test_disjunctive_nonadditive_aggregates_rejected():
    tbl = table_lib.from_columns("s", synth.sessions_table(5_000, seed=3))
    db = _engine_with_family(tbl)
    pred = Predicate((
        Conjunction((Atom("OS", CmpOp.EQ, "os0"),)),
        Conjunction((Atom("OS", CmpOp.EQ, "os1"),)),
    ))
    for agg, vc in ((AggOp.AVG, "SessionTime"),
                    (AggOp.QUANTILE, "SessionTime")):
        q = Query("s", agg, vc, predicate=pred, bound=ErrorBound(0.2))
        with pytest.raises(ValueError, match="additive"):
            db.query(q)
        with pytest.raises(ValueError, match="additive"):
            db.query_batch([q])
    # additive aggregates still work
    ans = db.query(Query("s", AggOp.COUNT, predicate=pred,
                         bound=ErrorBound(0.2)))
    assert ans.groups


def test_prefix_for_k_uses_host_mirror():
    tbl = table_lib.from_columns("s", synth.sessions_table(8_000, seed=5))
    fam = samp.build_family(tbl, ("City",), k1=300.0, m=3, seed=1)
    assert isinstance(fam.entry_key_host, np.ndarray)
    want = int(np.searchsorted(np.asarray(fam.entry_key), fam.ks[1]))
    assert fam.prefix_for_k(fam.ks[1]) == want
    # merge keeps the mirror in sync
    delta = tbl.append(synth.sessions_table(500, seed=6))
    fam, _ = samp.merge_family(fam, delta.columns,
                               samp.delta_units(500, 1, 1))
    np.testing.assert_array_equal(fam.entry_key_host,
                                  np.asarray(fam.entry_key))
