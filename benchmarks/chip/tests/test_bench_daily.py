"""conviva_daily.dashboard_open at a small size on the CPU: families of
more than 4,096 strata served by the fused kernels (interpret mode) agree
with the plain reference, the scans say which families they read, the mix
holds its shares and its schedule, and a whole run is correct.

At 60,000 rows the (dt, URL) family has about 8,250 strata and (dt, City)
about 4,190: both past 4,096. K1 is cut with the rows so that strata are
capped and the Horvitz-Thompson weights differ from 1.
"""
import json
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import checks, harness, traffic  # noqa: E402
from blinkbench.reference import Reference  # noqa: E402
from test_bench_faults import quiet_cache  # noqa: E402, F401

CELL = "conviva_daily.dashboard_open"
ROWS = 60_000
SEED = 2 ** 31 + 16
SMALL = {"k1": 20.0, "storage_budget_fraction": 1.5}


@pytest.fixture(scope="module")
def served():
    """Forty queries of the mix, each through `BlinkDB.query` on the fused
    kernels, traced."""
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.obs.trace import QueryTrace, activate
    from repro.service.parser import parse_blinkql
    cell = harness.load_cell(harness.ROOT, CELL)
    cfg = {**cell.cfg, "engine": {**cell.cfg["engine"], **SMALL}}
    assert cfg["engine"]["use_pallas"]
    data = cell.generator.generate(cfg, SEED, ROWS)
    db = harness.build_engine(cfg, harness.make_table(data))
    mix = traffic.Mix(cell.mix_spec, data)
    records = []
    for spec in mix.queries(np.random.default_rng(1), 40):
        tr = QueryTrace(spec.text, reason="forced")
        with activate(tr):
            ans = db.query(parse_blinkql(spec.text, db).normalized())
        tr.finish()
        rec = harness.Record(spec, 0.0)
        rec.answer, rec.trace = ans, tr
        records.append(rec)
    return records, Reference(data, cfg), db


def test_both_day_families_are_wide(served):
    _, _, db = served
    strata = {p: f.n_distinct for p, f in db.families["sessions"].items()}
    assert set(strata) == {("City", "dt"), ("URL", "dt"), ()}
    assert 4096 < strata[("City", "dt")] <= 6000
    assert 8000 < strata[("URL", "dt")] <= 15000


def test_wide_families_agree_with_the_reference(served):
    records, ref, db = served
    from repro.kernels import ops as kops
    assert kops.INTERPRET   # the Pallas kernels, interpreted on the CPU
    wide = 0
    for r in records:
        a = r.answer
        if checks.is_exact(a):
            want = ref.exact(r.spec)
        else:
            want = ref.sampled(r.spec, tuple(a.sample_phi), a.sample_k)
            fam = db.families["sessions"][tuple(a.sample_phi)]
            wide += fam.n_distinct > 4096
        rows, est = checks.gaps(a.groups, want)
        assert rows == 0, r.spec.text
        assert est <= checks.LIMITS["estimate_gap"], (r.spec.text, est)
    assert wide >= 20


def test_scan_spans_name_the_family_strata(served):
    """Every `scan.launch` and `scan.wait` carries the scanned family's
    stratum count (0 for the base table), and the rows counter splits by
    it: the wide and narrow parts add up to every row read."""
    records, _, db = served
    counts = {f.n_distinct for f in db.families["sessions"].values()} | {0}
    seen = Counter()
    for r in records:
        for s in r.trace.spans:
            if s.name in ("scan.launch", "scan.wait"):
                assert s.attrs["strata"] in counts
                seen[s.name, s.attrs["strata"] > 4096] += 1
    assert seen["scan.launch", True] == seen["scan.wait", True] > 0
    rows = db.metrics.counter("engine_scanned_rows_total",
                              labels=("strata",))
    wide, narrow = rows.value("wide"), rows.value("narrow")
    assert wide > 0
    assert wide + narrow == \
        db.metrics.counter("engine_rows_read_total").value()


def test_mix_shares_are_exact_and_schedule_fixed():
    cell = harness.load_cell(harness.ROOT, CELL)
    data = cell.generator.generate(cell.cfg, 12345, 20_000)
    mix = traffic.Mix(cell.mix_spec, data)
    n = 1000
    specs = mix.queries(np.random.default_rng(9), n)

    def share(key):
        got = Counter(key(s) for s in specs)
        return {k: v / n for k, v in got.items()}
    shapes = share(lambda s: s.shape)
    for name, w in [("day_city", 0.4), ("day_url", 0.3),
                    ("day_city_os", 0.1), ("day_by_city", 0.2)]:
        assert shapes[name] == pytest.approx(w, abs=1 / n)
    aggs = share(lambda s: (s.agg, s.level))
    for key, w in [(("COUNT", None), 0.4), (("AVG", None), 0.3),
                   (("SUM", None), 0.2), (("QUANTILE", 0.5), 0.05),
                   (("QUANTILE", 0.9), 0.05)]:
        assert aggs[key] == pytest.approx(w, abs=4 / n)
    bounds = share(lambda s: (s.bound, s.eps, s.seconds))
    for key, w in [(("error", 0.1, None), 0.35), (("error", 0.05, None), 0.15),
                   (("time", None, 1.0), 0.2), (("time", None, 2.0), 0.1),
                   (("none", None, None), 0.2)]:
        assert bounds[key] == pytest.approx(w, abs=4 / n)
    for s in specs:
        cols = [c for c, _, _ in s.atoms]
        assert cols[0] == "dt"
        assert (s.group_by == "City") == (s.shape == "day_by_city")
    other = traffic.Mix(cell.mix_spec, cell.generator.generate(
        cell.cfg, 2 ** 31 + 9, 20_000))
    assert mix.window(51.0) == other.window(51.0)


def _span(name, t0, t1, **attrs):
    return SimpleNamespace(name=name, t0=t0, t1=t1, attrs=attrs)


def test_wide_share_reader():
    read = harness.load_module(BENCH / "metrics" / "wide_strata_scan_share.py",
                               "wide_strata_scan_share").read

    def rec(spans, answered=True):
        answer = (SimpleNamespace(trace=SimpleNamespace(spans=spans))
                  if answered else None)
        return harness.Record(spec=None, due=0.0, done=1.0, answer=answer)
    a = rec([_span("scan", 0.0, 4.0), _span("scan.launch", 0.0, 1.0,
                                            strata=15000),
             _span("scan.wait", 1.0, 3.0, strata=15000)])
    b = rec([_span("scan.launch", 5.0, 5.5, strata=200),
             _span("scan.wait", 5.5, 6.5, strata=0),
             _span("fetch", 6.5, 9.0)])
    gone = rec([_span("scan.wait", 0.0, 9.0, strata=15000)], answered=False)
    view = harness.RunView([a, b, gone], (0.0, 10.0), {}, None, None, {},
                           "TPU v5 lite")
    assert read(view) == pytest.approx(3.0 / 4.5)
    # a program whose spans carry no stratum count reads nothing
    bare = rec([_span("scan.launch", 0.0, 1.0), _span("scan.wait", 1.0, 2.0)])
    assert read(harness.RunView([bare], (0.0, 10.0), {}, None, None, {},
                                "TPU v5 lite")) is None


def test_whole_traced_run_is_correct(quiet_cache, capsys):
    """A traced run of the cell, minus the look for a chip: correct, the
    wide families carry most of the scan time, and the bfloat16 control of
    the same answers is not correct."""
    rc = harness.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                       "4", "--trace", "1"], require_tpu=False, n_rows=ROWS,
                      engine_overrides=SMALL, controls=("bf16",))
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True, out.err[-2000:]
    assert result["failed"] == 0
    share = result["metrics"]["wide_strata_scan_share.bound_met"]["value"]
    assert 0.5 <= share <= 1.0
    assert "control bf16: correct=False" in out.err
