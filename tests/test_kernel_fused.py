"""Fused memory-lean scan kernel: parity, narrow dtypes, one-pass quantiles.

Interpret-mode sweeps (the repo's substitute for hypothesis, which is not
installed: seeded parametrized cases) covering the in-kernel HT derivation
against ref.agg_scan_fused_ref, bit-identity with the pre-fusion batched
kernel, narrow dtype widths, the 127-atom template limit, padding rows
(entry_key=+inf), ghost slots, 1-stratum freq tables, families of up to
every int16 stratum id (and the executor's programs keeping them on the
kernel), the fused quantile
kernel, the grouped_quantile empty-selection guard, and the engine's
one-pass QUANTILE execution (observable through its program caches).
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AggOp, Atom, BlinkDB, CmpOp, EngineConfig, Predicate,
                        Query)
from repro.core import executor as exec_lib
from repro.core import table as table_lib
from repro.data import synth
from repro.kernels import ref
from repro.kernels.agg_scan import (CONST_LANES, MAX_FUSED_STRATA,
                                    agg_scan_batched_pallas,
                                    agg_scan_fused_pallas,
                                    quantile_scan_pallas, visit_plan)


def _fused_case(rng, n, n_groups, q, n_atoms, n_strata=37,
                strat_dtype=np.int8, atom_dtype=np.int8, ghost_frac=0.0):
    values = jnp.asarray(rng.normal(5, 2, n).astype(np.float32))
    unit = jnp.asarray(rng.random(n).astype(np.float32))
    strat = jnp.asarray(rng.integers(0, n_strata, n).astype(strat_dtype))
    ftab = jnp.asarray(rng.integers(1, 500, n_strata).astype(np.float32))
    valid = jnp.asarray(rng.random(n) >= ghost_frac)
    codes = jnp.asarray(rng.integers(0, n_groups, n).astype(atom_dtype))
    atoms = tuple(jnp.asarray(rng.integers(0, 8, n).astype(atom_dtype))
                  for _ in range(n_atoms))
    ks = jnp.asarray(rng.uniform(20, 400, q).astype(np.float32))
    consts = jnp.asarray(rng.integers(0, 8, (q, n_atoms)).astype(np.float32))
    return values, unit, strat, ftab, valid, atoms, codes, ks, consts


def _assert_fused_parity(args, ops_struct, n_groups, atom_slots=None, **kw):
    got = agg_scan_fused_pallas(*args, ops_struct=ops_struct,
                                atom_slots=atom_slots, n_groups=n_groups,
                                interpret=True, **kw)
    want = ref.agg_scan_fused_ref(*args, ops_struct=ops_struct,
                                  atom_slots=atom_slots, n_groups=n_groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-3)
    return got


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("n", [1, 100, 5000])
@pytest.mark.parametrize("n_groups", [1, 600])
def test_fused_kernel_shapes(n, n_groups):
    rng = np.random.default_rng(n * 7 + n_groups)
    args = _fused_case(rng, n, n_groups, q=5, n_atoms=2)
    _assert_fused_parity(args, ((CmpOp.EQ,), (CmpOp.GT,)), n_groups)


@pytest.mark.parametrize("strat_dtype", [np.int8, np.int16, np.int32])
@pytest.mark.parametrize("atom_dtype", [np.int8, np.int16, np.int32])
def test_fused_kernel_narrow_dtype_widths(strat_dtype, atom_dtype):
    """Stored width must not change results: the kernel widens in VMEM."""
    rng = np.random.default_rng(11)
    args = _fused_case(rng, 4096, 12, q=4, n_atoms=2,
                       strat_dtype=strat_dtype, atom_dtype=atom_dtype)
    _assert_fused_parity(args, ((CmpOp.EQ, CmpOp.LE),), 12)


def test_fused_kernel_ghost_slots():
    """Tombstoned slots (valid=False) must contribute to no statistic."""
    rng = np.random.default_rng(12)
    args = _fused_case(rng, 3000, 8, q=3, n_atoms=1, ghost_frac=0.35)
    _assert_fused_parity(args, ((CmpOp.GT,),), 8)


def test_fused_kernel_all_ghosts_and_padding():
    """All-invalid input (every row a ghost) + implicit padding rows: the
    pad fill (unit=+inf) and valid=False must both zero the output."""
    rng = np.random.default_rng(13)
    v, u, s, ftab, _, atoms, c, ks, consts = _fused_case(
        rng, 1000, 4, q=2, n_atoms=1)
    dead = jnp.zeros(1000, bool)
    got = agg_scan_fused_pallas(v, u, s, ftab, dead, atoms, c, ks, consts,
                                ops_struct=((CmpOp.GT,),), n_groups=4,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_fused_kernel_one_stratum():
    """Degenerate 1-entry freq table (single stratum, strat all zero)."""
    rng = np.random.default_rng(14)
    args = _fused_case(rng, 2500, 6, q=3, n_atoms=1, n_strata=1)
    _assert_fused_parity(args, ((CmpOp.EQ,),), 6)


def test_fused_kernel_multichunk_freq_table():
    """freq table wider than one 128-lane row (the row select runs)."""
    rng = np.random.default_rng(15)
    args = _fused_case(rng, 4000, 10, q=3, n_atoms=1, n_strata=300,
                       strat_dtype=np.int16)
    _assert_fused_parity(args, ((CmpOp.LE,),), 10)


def test_fused_kernel_no_predicate():
    rng = np.random.default_rng(16)
    v, u, s, ftab, va, _, c, ks, _ = _fused_case(rng, 3000, 5, q=3, n_atoms=0)
    args = (v, u, s, ftab, va, (), c, ks, jnp.zeros((3, 0), jnp.float32))
    _assert_fused_parity(args, (), 5)


def test_fused_kernel_atom_slot_dedup():
    """Two template atoms on ONE streamed column block (slot sharing)."""
    rng = np.random.default_rng(17)
    v, u, s, ftab, va, atoms, c, ks, _ = _fused_case(rng, 2000, 4, q=2,
                                                     n_atoms=1)
    consts = jnp.asarray(rng.integers(0, 8, (2, 2)).astype(np.float32))
    args = (v, u, s, ftab, va, atoms, c, ks, consts)
    _assert_fused_parity(args, ((CmpOp.GE, CmpOp.LE),), 4,
                         atom_slots=(0, 0))


def test_fused_kernel_127_atom_limit():
    """The qconst layout admits exactly CONST_LANES-1 = 127 atoms."""
    rng = np.random.default_rng(18)
    n, n_atoms = 512, CONST_LANES - 1
    v, u, s, ftab, va, atoms, c, ks, _ = _fused_case(rng, n, 2, q=1,
                                                     n_atoms=1)
    consts = jnp.asarray(rng.integers(0, 8, (1, n_atoms)).astype(np.float32))
    struct = ((CmpOp.GE,) * n_atoms,)
    args = (v, u, s, ftab, va, atoms, c, ks, consts)
    _assert_fused_parity(args, struct, 2, atom_slots=(0,) * n_atoms)
    # one more atom must be rejected, not silently mis-addressed
    with pytest.raises(ValueError, match="atoms"):
        agg_scan_fused_pallas(v, u, s, ftab, va, atoms, c, ks,
                              jnp.zeros((1, n_atoms + 1), jnp.float32),
                              ops_struct=((CmpOp.GE,) * (n_atoms + 1),),
                              atom_slots=(0,) * (n_atoms + 1), n_groups=2,
                              interpret=True)


def _wide_case(n_strata, q):
    """A family with `n_strata` strata (int16 ids, first and last present)
    and stratum sizes up to 1e7, so a freq off by one ulp shows."""
    rng = np.random.default_rng(n_strata)
    n = 2 * n_strata + 1000
    v, u, _, _, va, atoms, c, _, consts = _fused_case(
        rng, n, 9, q=q, n_atoms=1, atom_dtype=np.int16, ghost_frac=0.1)
    ids = rng.integers(0, n_strata, n)
    ids[:2] = 0, n_strata - 1
    s = jnp.asarray(ids.astype(np.int16))
    ftab = jnp.asarray(rng.integers(1, 10 ** 7, n_strata).astype(np.float32))
    ks = jnp.asarray(rng.uniform(1e3, 1e7, q).astype(np.float32))
    return v, u, s, ftab, va, atoms, c, ks, consts


@pytest.mark.parametrize("n_strata", [4097, 6016, 15000, MAX_FUSED_STRATA])
def test_fused_kernels_wide_families(n_strata):
    """Families past 4,096 strata, up to every int16 id: both fused
    kernels derive freq bit-identically to `freq_table[strat]` (the fused
    scan equals the pre-fusion kernel fed the gathered freq, bit for bit;
    the quantile kernel's moments equal the fused scan's) and match the
    oracle of kernels/ref.py."""
    v, u, s, ftab, va, atoms, c, ks, consts = args = _wide_case(n_strata, 3)
    struct = ((CmpOp.LE,),)
    fused = _assert_fused_parity(args, struct, 9)
    freq = ftab[s.astype(jnp.int32)]
    ek = jnp.where(va, u * freq, jnp.inf)
    old = agg_scan_batched_pallas(v, freq, ek, atoms[0][None].astype(
        jnp.float32), c.astype(jnp.int32), ks, consts, ops_struct=struct,
        n_groups=9, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(old))
    lo, hi = float(np.asarray(v).min()), float(np.asarray(v).max())
    mom, hist = quantile_scan_pallas(
        v, u, s, ftab, va, atoms, c, ks[0], jnp.float32(lo), jnp.float32(hi),
        consts[0], ops_struct=struct, n_groups=9, interpret=True)
    np.testing.assert_array_equal(np.asarray(mom), np.asarray(fused[0]))
    w = ((ek < ks[0]) & (atoms[0] <= consts[0, 0])) / jnp.minimum(
        1.0, ks[0] / freq)
    want = ref.quantile_hist_ref(v, w, c.astype(jnp.int32), 9, lo, hi, 256)
    np.testing.assert_allclose(np.asarray(hist), np.asarray(want).T,
                               rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("builder", ["single", "batched", "quantile"])
def test_served_builders_scan_wide_families_on_the_kernel(builder):
    """The executor's programs for a 15,000-strata family run the fused
    Pallas kernel, never the jnp path."""
    n, d = 4096, 15000
    rng = np.random.default_rng(60)
    cols = {"dt": jnp.asarray(rng.integers(0, 30, (1, n)), jnp.int8),
            "URL": jnp.asarray(rng.integers(0, 500, (1, n)), jnp.int16),
            "SessionTime": jnp.asarray(rng.random((1, n)), jnp.float32)}
    tail = (cols, jnp.asarray(rng.random((1, n)), jnp.float32),
            jnp.asarray(rng.integers(0, d, (1, n)), jnp.int16),
            jnp.ones(d + 8, jnp.float32), jnp.ones((1, n), bool))
    struct = ((("dt", CmpOp.EQ), ("URL", CmpOp.EQ)),)
    vals = ((3.0, 7.0),)
    if builder == "single":
        fn = exec_lib.make_query_fn(struct, "SessionTime", None, 1,
                                    use_pallas=True)
        args = (jnp.float32(50.0), vals) + tail
    elif builder == "batched":
        fn = exec_lib.make_batched_query_fn(struct, "SessionTime", "dt", 30,
                                            use_pallas=True)
        args = (jnp.ones(2, jnp.float32), jnp.ones((2, 2), jnp.float32)) \
            + tail
    else:
        fn = exec_lib.make_quantile_fn(struct, "SessionTime", None, 1,
                                       use_pallas=True)
        args = (jnp.float32(50.0), vals, jnp.float32(0.5), jnp.float32(0.0),
                jnp.float32(1.0)) + tail
    assert "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_fused_kernel_rejects_tables_past_int16_ids():
    """A table longer than the int16 id space is refused at trace time,
    not scanned with a VMEM block the chip cannot hold."""
    v, u, s, _, va, atoms, c, ks, consts = _wide_case(4097, 1)
    big = jnp.ones(MAX_FUSED_STRATA + 1, jnp.float32)
    with pytest.raises(ValueError, match="strata"):
        agg_scan_fused_pallas(v, u, s, big, va, atoms, c, ks, consts,
                              ops_struct=((CmpOp.EQ,),), n_groups=9,
                              interpret=True)


def test_fused_bit_identical_to_prefusion_kernel():
    """Acceptance: given the SAME derived freq/entry_key the fused kernel's
    accumulation is bit-for-bit the pre-fusion batched kernel's — in-kernel
    derivation changes where freq/entry_key are computed, not a single bit
    of the reduction."""
    rng = np.random.default_rng(20)
    v, u, s, ftab, va, atoms, c, ks, consts = _fused_case(
        rng, 6000, 24, q=4, n_atoms=2, n_strata=200, strat_dtype=np.int16,
        ghost_frac=0.1)
    struct = ((CmpOp.EQ,), (CmpOp.GT,))
    fused = agg_scan_fused_pallas(v, u, s, ftab, va, atoms, c, ks, consts,
                                  ops_struct=struct, n_groups=24,
                                  interpret=True)
    freq = ftab[s.astype(jnp.int32)]
    ek = jnp.where(va, u * freq, jnp.inf)
    old_atoms = jnp.stack([a.astype(jnp.float32) for a in atoms])
    old = agg_scan_batched_pallas(v, freq, ek, old_atoms, c.astype(jnp.int32),
                                  ks, consts, ops_struct=struct, n_groups=24,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(old))


# -------------------------------------------------------- quantile kernel

def test_quantile_kernel_moments_match_fused_scan():
    """The one-pass quantile kernel's moment half is bit-identical to the
    fused scan at the same k (same blocks, same accumulation order)."""
    rng = np.random.default_rng(30)
    v, u, s, ftab, va, atoms, c, ks, consts = _fused_case(
        rng, 5000, 10, q=1, n_atoms=1, ghost_frac=0.2)
    struct = ((CmpOp.LE,),)
    lo, hi = float(np.asarray(v).min()), float(np.asarray(v).max())
    mom, hist = quantile_scan_pallas(
        v, u, s, ftab, va, atoms, c, ks[0], jnp.float32(lo), jnp.float32(hi),
        consts[0], ops_struct=struct, n_groups=10, interpret=True)
    fused = agg_scan_fused_pallas(v, u, s, ftab, va, atoms, c, ks, consts,
                                  ops_struct=struct, n_groups=10,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(mom), np.asarray(fused[0]))
    assert hist.shape == (256, 10)


@pytest.mark.parametrize("case_seed", [0, 1, 2])
def test_quantile_kernel_histogram_matches_ref(case_seed):
    rng = np.random.default_rng(40 + case_seed)
    n, n_groups = 4000, 7
    v, u, s, ftab, va, atoms, c, ks, consts = _fused_case(
        rng, n, n_groups, q=1, n_atoms=1, ghost_frac=0.1)
    struct = ((CmpOp.LE,),)
    lo, hi = float(np.asarray(v).min()), float(np.asarray(v).max())
    mom, hist = quantile_scan_pallas(
        v, u, s, ftab, va, atoms, c, ks[0], jnp.float32(lo), jnp.float32(hi),
        consts[0], ops_struct=struct, n_groups=n_groups, interpret=True)
    # oracle weights: HT rates over the surviving prefix
    freq = ftab[s.astype(jnp.int32)]
    ek = jnp.where(va, u * freq, jnp.inf)
    pred = atoms[0].astype(jnp.float32) <= consts[0, 0]
    mask = (ek < ks[0]) & pred
    w = mask / jnp.minimum(1.0, ks[0] / freq)
    want = ref.quantile_hist_ref(v, w, c.astype(jnp.int32), n_groups,
                                 lo, hi, 256)
    np.testing.assert_allclose(np.asarray(hist), np.asarray(want).T,
                               rtol=2e-5, atol=1e-3)
    # histogram mass == weighted selection mass (nothing lost to clipping)
    np.testing.assert_allclose(np.asarray(hist).sum(),
                               float(jnp.sum(w)), rtol=2e-5, atol=1e-2)


def test_quantile_kernel_bins_must_be_lane_aligned():
    rng = np.random.default_rng(50)
    v, u, s, ftab, va, atoms, c, ks, consts = _fused_case(rng, 256, 2, 1, 1)
    with pytest.raises(ValueError, match="128"):
        quantile_scan_pallas(v, u, s, ftab, va, atoms, c, ks[0],
                             jnp.float32(0), jnp.float32(1), consts[0],
                             ops_struct=((CmpOp.LE,),), n_groups=2,
                             n_bins=100, interpret=True)


# --------------------------------------- grouped_quantile empty-selection

def test_grouped_quantile_empty_selection_is_defined():
    """Regression: with NO selected row, lo/hi were ±inf (NaN bin indices)
    and all-masked groups divided by the clamped total. Must be (0, 0)."""
    v = jnp.asarray(np.linspace(0, 10, 64, dtype=np.float32))
    w = jnp.zeros(64, jnp.float32)
    g = jnp.asarray(np.arange(64) % 4, dtype=jnp.int32)
    qv, dens = exec_lib.grouped_quantile(v, w, g, 4, 0.5)
    np.testing.assert_array_equal(np.asarray(qv), 0.0)
    np.testing.assert_array_equal(np.asarray(dens), 0.0)
    assert np.all(np.isfinite(np.asarray(qv)))


def test_grouped_quantile_one_empty_group():
    """A single all-masked group yields (0, 0) without disturbing others."""
    rng = np.random.default_rng(60)
    v = jnp.asarray(rng.normal(5, 2, 512).astype(np.float32))
    g = jnp.asarray((np.arange(512) % 3).astype(np.int32))
    w = jnp.asarray((np.asarray(g) != 1).astype(np.float32))
    qv, dens = exec_lib.grouped_quantile(v, w, g, 3, 0.5)
    qv, dens = np.asarray(qv), np.asarray(dens)
    assert qv[1] == 0.0 and dens[1] == 0.0
    for gi in (0, 2):
        sel = np.sort(np.asarray(v)[np.asarray(g) == gi])
        assert abs(qv[gi] - np.median(sel)) < (sel.max() - sel.min()) / 16


def test_hist_to_quantile_empty_guard():
    hist = jnp.zeros((3, 256), jnp.float32)
    qv, dens = exec_lib.hist_to_quantile(hist, 0.0, 1.0, 0.5)
    np.testing.assert_array_equal(np.asarray(qv), 0.0)
    np.testing.assert_array_equal(np.asarray(dens), 0.0)


# ----------------------------------------------- engine: one-pass QUANTILE

@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_quantile_runs_one_pass(use_pallas):
    """A QUANTILE query on the clean path compiles ONLY the fused one-pass
    program: the plain scan cache stays empty for its template (the old
    engine ran a moments scan + a second full-column quantile pass)."""
    tbl = table_lib.from_columns("s", synth.sessions_table(15_000, seed=3))
    db = BlinkDB(EngineConfig(k1=400.0, m=3, seed=1, use_pallas=use_pallas))
    db.register_table("s", tbl)
    db.add_family("s", ("City",))
    q = Query("s", AggOp.QUANTILE, value_column="SessionTime", quantile=0.5,
              predicate=Predicate.where(
                  Atom("City", CmpOp.EQ, tbl.dictionaries["City"][0])))
    ans = db.query(q)
    assert ans.groups and np.isfinite(ans.groups[0].estimate)
    assert db._quantile_programs, "fused quantile program not compiled"
    assert not db._programs, \
        "QUANTILE compiled a separate moments scan (second pass)"


def test_engine_quantile_pallas_matches_jnp_roughly():
    """Histogram binning differs (family-global vs selection-local range) so
    the two paths agree to bin resolution, not bitwise."""
    tbl = table_lib.from_columns("s", synth.sessions_table(15_000, seed=3))
    answers = {}
    for up in (False, True):
        db = BlinkDB(EngineConfig(k1=400.0, m=3, seed=1, use_pallas=up))
        db.register_table("s", tbl)
        db.add_family("s", ())
        q = Query("s", AggOp.QUANTILE, value_column="SessionTime",
                  quantile=0.5)
        answers[up] = db.query(q).groups[0].estimate
    col = np.asarray(tbl.columns["SessionTime"])
    span = float(col.max() - col.min())
    assert abs(answers[True] - answers[False]) <= span / 64


# ------------------------------------------ row grid bounded by the key prefix

BOUND_BLOCK = 256   # kernel row block in these cases (the engine's is 1024)


def _sorted_case(n, n_groups, n_strata):
    """A family block in entry-key order, keys unit·freq all distinct."""
    rng = np.random.default_rng(n_groups * 31 + n_strata)
    strat = rng.integers(0, n_strata, n).astype(
        np.int8 if n_strata <= 127 else np.int16)
    ftab = rng.integers(50, 5000, n_strata).astype(np.float32)
    unit = rng.random(n).astype(np.float32)
    keys = unit * ftab[strat]
    order = np.argsort(keys, kind="stable")
    unit, strat, keys = unit[order], strat[order], keys[order]
    keep = np.concatenate([[True], np.diff(keys) > 0])
    unit, strat, keys = unit[keep], strat[keep], keys[keep]
    n = keys.size
    values = rng.normal(5, 2, n).astype(np.float32)
    codes = rng.integers(0, n_groups, n).astype(np.int16)
    atom = rng.integers(0, 4, n).astype(np.int8)
    return values, unit, strat, ftab, codes, atom, keys


def _k_for(case, keys):
    """The case's resolution cap, chosen against the stored keys."""
    up = lambda x: np.nextafter(np.float32(x), np.float32(np.inf))    # noqa: E731
    down = lambda x: np.nextafter(np.float32(x), np.float32(-np.inf))  # noqa: E731
    return {"prefix0": keys[0], "prefix1": keys[1],
            "block": keys[BOUND_BLOCK], "block+1": keys[BOUND_BLOCK + 1],
            "at_key": keys[700], "ulp_below": down(keys[700]),
            "ulp_above": up(keys[700]), "top": 2 * keys[-1],
            "q2": keys[900], "groups200": keys[1300],
            "strata15000": keys[500]}[case]


@pytest.mark.parametrize("kernel", ["fused", "quantile"])
@pytest.mark.parametrize("case", ["prefix0", "prefix1", "block", "block+1",
                                  "at_key", "ulp_below", "ulp_above", "top",
                                  "q2", "groups200", "strata15000"])
def test_bounded_row_grid_is_bit_identical(kernel, case):
    """A scan bounded by row_spans at the batch's largest k returns, bit
    for bit, what the full grid returns, and matches kernels/ref.py. Rows
    past the visited blocks are poisoned with NaN values in the bounded
    run: they are never read, or the output would not be finite."""
    n_groups = 200 if case == "groups200" else 7
    n_strata = 15000 if case == "strata15000" else 40
    v, u, s, ftab, c, atom, keys = _sorted_case(3000, n_groups, n_strata)
    n = keys.size
    k = np.float32(_k_for(case, keys))
    ks = np.array([k, keys[300]] if case == "q2" else [k], np.float32)
    if kernel == "quantile":
        ks = ks[:1]
    spans = exec_lib.row_spans(types.SimpleNamespace(
        key_floor=keys, n_sorted=n, n_rows=n, n_shards=1), float(ks.max()))
    below = int(np.sum(keys < ks.max()))
    assert spans[0] >= below and spans[1] == spans[2] == spans[0]
    steps = int(visit_plan(spans, -(-n // BOUND_BLOCK), BOUND_BLOCK,
                           xp=np)[2])
    assert steps == -(-below // BOUND_BLOCK)
    poisoned = v.copy()
    poisoned[steps * BOUND_BLOCK:] = np.nan
    va = np.ones(n, bool)
    struct = ((CmpOp.LE,),)
    consts = np.full((ks.size, 1), 2.0, np.float32)
    common = (u, s, ftab, va, (atom,), c)
    kw = dict(ops_struct=struct, n_groups=n_groups, block_rows=BOUND_BLOCK,
              interpret=True)
    if kernel == "fused":
        full = agg_scan_fused_pallas(v, *common, ks, consts, **kw)
        got = agg_scan_fused_pallas(poisoned, *common, ks, consts,
                                    row_spans=spans, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
        want = ref.agg_scan_fused_ref(v, *common, ks, consts,
                                      ops_struct=struct, n_groups=n_groups)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-3)
        return
    lo, hi = float(v.min()), float(v.max())
    qargs = (ks[0], np.float32(lo), np.float32(hi), consts[0])
    full = quantile_scan_pallas(v, *common, *qargs, **kw)
    got = quantile_scan_pallas(poisoned, *common, *qargs, row_spans=spans,
                               **kw)
    for a, b in zip(got, full):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mom_want = ref.agg_scan_fused_ref(v, *common, ks, consts,
                                      ops_struct=struct, n_groups=n_groups)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(mom_want[0]),
                               rtol=2e-5, atol=1e-3)
    freq = ftab[s.astype(np.int64)]
    w = ((u * freq < ks[0]) & (atom <= 2.0)) / np.minimum(1.0, ks[0] / freq)
    hist = ref.quantile_hist_ref(jnp.asarray(v), jnp.asarray(w, jnp.float32),
                                 jnp.asarray(c, jnp.int32), n_groups, lo, hi,
                                 256)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(hist).T,
                               rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_row_spans_cover_every_slot_a_scan_can_pass(n_shards):
    """Every slot whose key passes `key < float32(k)` lies in the spans'
    local rows (slot j at local row j // S), key-ordered head and
    appended tail alike; the head holds no more than one local row past
    the last passing head slot."""
    rng = np.random.default_rng(n_shards)
    n_sorted, n_rows = 5000, 5600
    head = np.sort(rng.random(n_sorted).astype(np.float32) * 1000)
    keys = np.concatenate([head, rng.random(n_rows - n_sorted).astype(
        np.float32) * 1000])
    striped = types.SimpleNamespace(key_floor=head, n_sorted=n_sorted,
                                    n_rows=n_rows, n_shards=n_shards)
    local = np.arange(n_rows) // n_shards
    for k in [0.0, float(head[0]), float(head[1234]), 250.5, 999.0, 1e4]:
        spans = exec_lib.row_spans(striped, k)
        passing = local[keys < np.float32(k)]
        inside = (passing < spans[0]) | ((passing >= spans[1])
                                         & (passing < spans[2]))
        assert inside.all(), (k, spans)
        last = np.flatnonzero(head < np.float32(k))
        assert spans[0] == (0 if last.size == 0
                            else -(-(last[-1] + 1) // n_shards))
        assert (spans[1], spans[2]) == (n_sorted // n_shards,
                                        -(-n_rows // n_shards))
