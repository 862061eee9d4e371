"""The traffic generator: deterministic from the seed, exact shares, valid
BlinkQL for the program's parser, parameters inside their stated ranges."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import harness, traffic  # noqa: E402

CELLS = ["conviva.mixed_open", "tpch.range_open"]


def small_cell(name: str, rows: int = 20_000):
    cell = harness.load_cell(harness.ROOT, name)
    data = cell.generator.generate(cell.cfg, 12345, rows)
    return cell, traffic.Mix(cell.mix_spec, data)


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_queries(name):
    """A seed gives the same data; the window's schedule comes from the
    mix's pool seed alone."""
    cell, mix = small_cell(name)
    again = traffic.Mix(cell.mix_spec,
                        cell.generator.generate(cell.cfg, 12345, 20_000))
    for col, x in mix.data.columns.items():
        np.testing.assert_array_equal(x, again.data.columns[col])
    a, b = mix.window(30.0), again.window(30.0)
    assert len(a) == round(mix.spec["rate_qps"] * 30.0)
    assert a == b
    assert [t for t, _ in a] == sorted(t for t, _ in a)
    assert a[-1][0] <= 30.0


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_sends_the_same_work(name):
    """Seeds draw the data's measures; every seed sends the same queries
    at the same times, so no seed changes the work or the queueing."""
    cell, mix = small_cell(name)
    other = traffic.Mix(cell.mix_spec, cell.generator.generate(
        cell.cfg, 2 ** 31 + 9, 20_000))
    assert mix.window(30.0) == other.window(30.0)
    numeric = [c for c, k in mix.data.kinds.items() if k == "numeric"]
    assert any(not np.array_equal(mix.data.columns[c], other.data.columns[c])
               for c in numeric)


def _shares(specs, key):
    got = Counter(key(s) for s in specs)
    return {k: v / len(specs) for k, v in got.items()}


@pytest.mark.parametrize("name", ["conviva.mixed_open", "tpch.range_open"])
def test_shares_are_exact(name):
    cell, mix = small_cell(name)
    n = 600
    specs = mix.queries(np.random.default_rng(5), n)
    shapes = mix.spec["shapes"]
    total = sum(s["weight"] for s in shapes)
    got = _shares(specs, lambda s: s.shape)
    for s in shapes:
        assert abs(got[s["name"]] - s["weight"] / total) <= 1.0 / n
    # bounds: each shape draws its bound options by quota
    b = _shares(specs, lambda s: (s.bound, s.eps, s.seconds))
    bounds = mix.spec["bounds"]
    bw = sum(x["weight"] for x in bounds)
    for x in bounds:
        key = ("error", x["error_pct"] / 100, None) if "error_pct" in x else \
            ("time", None, float(x["seconds"])) if "seconds" in x else \
            ("none", None, None)
        assert abs(b[key] - x["weight"] / bw) <= len(shapes) / n


def test_conviva_aggregate_and_group_shares():
    cell, mix = small_cell("conviva.mixed_open")
    n = 1000
    specs = mix.queries(np.random.default_rng(9), n)
    agg = _shares(specs, lambda s: (s.agg, s.level))
    assert abs(agg[("COUNT", None)] - 0.4) <= 5 / n
    assert abs(agg[("AVG", None)] - 0.3) <= 5 / n
    assert abs(agg[("SUM", None)] - 0.2) <= 5 / n
    assert abs(agg[("QUANTILE", 0.5)] - 0.05) <= 5 / n
    grp = _shares(specs, lambda s: s.group_by)
    assert abs(grp[None] - 0.8) <= 5 / n


def test_tpch_parameters_follow_the_substitution_rules():
    cell, mix = small_cell("tpch.range_open")
    specs = mix.queries(np.random.default_rng(11), 300)
    dec1 = 10561.0   # 1998-12-01 as days since 1970-01-01
    for s in specs:
        atoms = {(c, op): v for c, op, v in s.atoms}
        if s.shape == "q1":
            assert dec1 - 120 <= atoms[("l_shipdate", "<=")] <= dec1 - 60
            assert s.group_by == "l_returnflag"
            assert s.agg in ("AVG", "SUM")
        elif s.shape == "q6":
            lo, hi = atoms[("l_shipdate", ">=")], atoms[("l_shipdate", "<")]
            assert hi - lo == 365
            d_lo, d_hi = atoms[("l_discount", ">=")], atoms[("l_discount", "<=")]
            assert abs((d_hi - d_lo) - 0.02) < 1e-9
            assert 0.02 - 1e-9 <= (d_lo + d_hi) / 2 <= 0.09 + 1e-9
            assert atoms[("l_quantity", "<")] in (24, 25)
        else:
            assert s.agg == "COUNT" and s.value_column is None


@pytest.mark.parametrize("name", CELLS)
def test_texts_parse_and_warm_up_covers_every_shape(name):
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.service.parser import parse_blinkql
    cell, mix = small_cell(name)
    db = type("DB", (), {"tables": {cell.cfg["table"]:
                                    harness.make_table(mix.data)}})()
    for s in mix.queries(np.random.default_rng(3), 50):
        q = parse_blinkql(s.text, db)
        assert q.agg.name == s.agg
        assert len(q.predicate.disjuncts[0].atoms) == len(s.atoms)


@pytest.mark.parametrize("name", CELLS)
def test_a_query_repeats_under_other_aggregates_and_bounds(name):
    """Set-up sends the window's own instantiations under other aggregates
    and bounds: `make(..., at=spec.at)` keeps every constant."""
    cell, mix = small_cell(name)
    shapes = {s["name"]: s for s in mix.spec["shapes"]}
    for _, spec in mix.window(20.0):
        shape = shapes[spec.shape]
        aggs = mix.aggregate_classes(shape)
        again = mix.make(shape, aggs[-1], {"column": spec.group_by},
                         {"error_pct": 1e-4}, at=spec.at)
        assert again.atoms == spec.atoms and again.group_by == spec.group_by
        assert len({(x["agg"] == "QUANTILE", x.get("column"))
                    for x in aggs}) == len(aggs)


def test_every_mix_file_is_used_by_a_cell():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    used = {w["traffic"] for w in bench["workloads"]}
    assert used == {p.stem for p in (BENCH / "traffic").glob("*.json")}
