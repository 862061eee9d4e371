"""Sample-family construction: paper §3.1 + Appendix A properties."""
import numpy as np
import pytest

from repro.core import sampling as samp
from repro.core import table as table_lib
from repro.data import synth


@pytest.fixture(scope="module")
def sessions():
    return table_lib.from_columns("sessions", synth.sessions_table(50_000, seed=3))


def test_family_nesting_and_prefixes(sessions):
    fam = samp.build_family(sessions, ("City",), k1=400.0, c=2.0, m=4)
    # ks descending, prefixes descending, prefix(K_i) consistent with entry_key
    assert list(fam.ks) == sorted(fam.ks, reverse=True)
    assert list(fam.prefix_sizes) == sorted(fam.prefix_sizes, reverse=True)
    ek = np.asarray(fam.entry_key)
    assert np.all(np.diff(ek) >= 0), "family must be sorted by entry key"
    for k, n in zip(fam.ks, fam.prefix_sizes):
        assert np.all(ek[:n] < k)
        if n < fam.n_rows:
            assert ek[n] >= k
    # Nesting: S(K_{i+1}) is literally a prefix of S(K_i).
    for a, b in zip(fam.prefix_sizes, fam.prefix_sizes[1:]):
        assert b <= a


def test_stratum_sizes_concentrate_at_k(sessions):
    """Poisson stratification: E[|stratum ∩ S(K)|] = min(F, K)."""
    k = 200.0
    fam = samp.build_family(sessions, ("City",), k1=k, c=2.0, m=1)
    city = np.asarray(fam.columns["City"])
    freq = np.asarray(fam.freq)
    counts = np.bincount(city, minlength=sessions.cardinality("City"))
    full = table_lib.stratum_frequencies(
        *reversed(table_lib.combined_codes(sessions, ("City",))[::-1]),
    ) if False else None
    codes, _ = table_lib.combined_codes(sessions, ("City",))
    full_counts = table_lib.stratum_frequencies(codes, int(codes.max()) + 1)
    for code, f in enumerate(full_counts):
        expected = min(f, k)
        got = counts[code] if code < len(counts) else 0
        if f <= k:
            assert got == f, "stratum below cap must be fully retained"
        else:
            # Binomial(F, K/F): sd = sqrt(K(1-K/F)) — allow 5 sigma
            sd = np.sqrt(k * (1 - k / f))
            assert abs(got - expected) <= 5 * sd + 1


def test_rates_are_exact_inclusion_probs(sessions):
    fam = samp.build_family(sessions, ("City",), k1=300.0, c=2.0, m=3)
    for k in fam.ks:
        rate = np.asarray(fam.rate(k))
        freq = np.asarray(fam.freq)
        np.testing.assert_allclose(rate, np.minimum(1.0, k / freq), rtol=1e-6)


def test_expected_rows_formula(sessions):
    codes, _ = table_lib.combined_codes(sessions, ("City", "OS"))
    freqs = table_lib.stratum_frequencies(codes, int(codes.max()) + 1)
    k = 150.0
    fam = samp.build_family(sessions, ("City", "OS"), k1=k, m=1)
    expect = samp.expected_sample_rows(freqs, k)
    sd = np.sqrt(expect)  # crude Poisson-ish bound
    assert abs(fam.n_rows - expect) < 6 * sd + 1


def test_uniform_family_is_uniform(sessions):
    fam = samp.build_uniform_family(sessions, fraction=0.25, m=2)
    assert fam.phi == ()
    assert abs(fam.n_rows / sessions.n_rows - 0.25) < 0.01
    # all rates equal at a given K
    r = np.asarray(fam.rate(fam.ks[0]))
    assert np.allclose(r, r[0])


def test_exact_k_reference(sessions):
    k = 50
    out = samp.stratified_exact_k(sessions, ("City",), k, seed=0)
    city = out["City"]
    rates = out["_rate"]
    codes, _ = table_lib.combined_codes(sessions, ("City",))
    full_counts = table_lib.stratum_frequencies(codes, int(codes.max()) + 1)
    got = np.bincount(city, minlength=len(full_counts))
    for code, f in enumerate(full_counts):
        expected = min(int(f), k)
        assert got[code] == expected, "exact-K keeps exactly min(F,K) rows"
    assert rates.min() > 0 and rates.max() <= 1.0


def test_zipf_storage_matches_paper_table5():
    """E6: Appendix A Table 5 (M=1e9). Paper rounds to 2 significant digits."""
    table5 = {
        (1.0, 1e4): 0.49, (1.0, 1e5): 0.58, (1.0, 1e6): 0.69,
        (1.5, 1e4): 0.024, (1.5, 1e5): 0.052, (1.5, 1e6): 0.114,
        (2.0, 1e4): 0.0038, (2.0, 1e5): 0.012, (2.0, 1e6): 0.038,
    }
    for (s, k), want in table5.items():
        got = samp.zipf_storage_fraction(s, k, 10 ** 9)
        assert abs(got - want) / want < 0.06, (s, k, got, want)


def test_family_properties_c_bound(sessions):
    """E7 (§3.1 properties): response-time proxy (rows read) of the chosen
    resolution is within ~factor c of the optimal-size sample; stddev within
    ~sqrt(c)."""
    c = 2.0
    fam = samp.build_family(sessions, ("City",), k1=2000.0, c=c, m=5)
    # For a spread of hypothetical optimal caps, the family's next-largest
    # resolution reads at most ~c× the optimal rows.
    ek = np.asarray(fam.entry_key)
    # Paper Appendix A assumes K_1 >= K_opt >= K_1/c^m (within family range).
    for k_opt in [130.0, 240.0, 555.0, 990.0, 1500.0]:
        rows_opt = np.searchsorted(ek, k_opt)
        k_chosen = min([k for k in fam.ks if k >= k_opt], default=fam.ks[0])
        rows_chosen = np.searchsorted(ek, k_chosen)
        assert rows_chosen <= c * rows_opt + len(fam.stratum_freqs), \
            (k_opt, k_chosen, rows_opt, rows_chosen)
        # error ratio: sd ∝ 1/sqrt(n_selected) ⇒ ratio ≤ sqrt(c) (+slack)
        assert np.sqrt(rows_chosen / max(rows_opt, 1)) <= np.sqrt(c) + 0.35


# Keys for the prefix search, each a function of the family and its mirror:
# for every one the count must equal the float64 comparison `ek < k`.
_PREFIX_KEYS = {
    "ks1": lambda fam, ek: fam.ks[1],
    "k1_third": lambda fam, ek: fam.ks[0] / 3,      # not a float32 value
    "stored": lambda fam, ek: float(ek[len(ek) // 2]),
    # one float64 ulp either side of a stored key: rounding the first to
    # float32 lands ON the stored key, which must still count as below it
    "stored_up": lambda fam, ek: float(np.nextafter(
        np.float64(ek[len(ek) // 2]), np.inf)),
    "stored_down": lambda fam, ek: float(np.nextafter(
        np.float64(ek[len(ek) // 2]), 0.0)),
    "zero": lambda fam, ek: 0.0,
    "above_all": lambda fam, ek: 2.0 * float(ek[-1]) + 1.0,
    "above_f32_range": lambda fam, ek: 1e40,
}


@pytest.fixture(scope="module")
def keyed_family():
    tbl = table_lib.from_columns("s", synth.sessions_table(8_000, seed=5))
    return samp.build_family(tbl, ("City",), k1=1000.0, m=3, seed=1)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("key", sorted(_PREFIX_KEYS))
def test_count_below_matches_float64_search(keyed_family, key, dtype):
    ek = keyed_family.entry_key_host.astype(dtype)
    fam = keyed_family.lazy_replace(entry_key_host=ek)
    k = _PREFIX_KEYS[key](keyed_family, ek)
    want = int(np.searchsorted(ek.astype(np.float64), k, side="left"))
    with np.errstate(all="raise"):
        assert samp.count_below(ek, k) == want
        assert fam.prefix_for_k(k) == want


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_count_below_searches_in_mirror_dtype(keyed_family, monkeypatch,
                                              dtype):
    """A key of another dtype makes NumPy copy the whole mirror on every
    search; what reaches searchsorted must already be the mirror's dtype."""
    ek = keyed_family.entry_key_host.astype(dtype)
    fam = keyed_family.lazy_replace(entry_key_host=ek)
    seen = []
    real = np.searchsorted

    def spy(a, v, *args, **kwargs):
        seen.append((a.dtype, np.asarray(v).dtype))
        return real(a, v, *args, **kwargs)

    monkeypatch.setattr(samp.np, "searchsorted", spy)
    fam.prefix_for_k(keyed_family.ks[0] / 3)
    samp.count_below(ek, 1e40)
    assert seen == [(np.dtype(dtype), np.dtype(dtype))] * 2
