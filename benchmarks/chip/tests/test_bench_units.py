"""The yardstick's fixed pieces: required bytes from schema and answer
alone, the peak table, the reference's rounding and the trace reduction's
interval arithmetic."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import devtrace, peaks, reference, traffic, widths  # noqa: E402

CONVIVA = json.loads((BENCH / "configs" / "conviva_sessions_20m.json")
                     .read_text())["columns"]
TPCH = json.loads((BENCH / "configs" / "tpch_lineitem_20m.json")
                  .read_text())["columns"]


def spec(agg="COUNT", value=None, atoms=(), group=None):
    return traffic.QuerySpec("", "s", agg, value, tuple(atoms), group, None,
                             "none", None, None, 0.95)


def test_required_bytes_from_schema_and_answer():
    # COUNT WHERE City = x on the (Genre) family: stratum id of 12 strata
    # (4 bits) + City at 200 values (8 bits) = 12 bits a row.
    q = spec(atoms=[("City", "=", "city007")])
    assert widths.required_bytes(CONVIVA, q, ("Genre",), 1000) == 1500.0
    # AVG(SessionTime) GROUP BY OS on the uniform family: f32 value + OS
    # (6 values: 3 bits), no stratum id.
    q = spec("AVG", "SessionTime", group="OS")
    assert widths.required_bytes(CONVIVA, q, (), 8) == 35.0
    # A column read twice (predicate and group) is read once.
    q = spec(atoms=[("OS", "=", "os1"), ("URL", "=", "url001")], group="OS")
    assert widths.required_bytes(CONVIVA, q, ("OS", "URL"), 8) == \
        8 * (12 + 3 + 9) / 8
    # TPC-H Q6 on (l_shipmode): 3 bits stratum, value f32, 3 numeric atoms
    # on two columns besides the value column.
    q = spec("SUM", "l_extendedprice",
             atoms=[("l_shipdate", ">=", 8401.0), ("l_shipdate", "<", 8766.0),
                    ("l_discount", ">=", 0.05), ("l_quantity", "<", 24.0)])
    assert widths.required_bytes(TPCH, q, ("l_shipmode",), 2) == \
        2 * (3 + 32 + 3 * 32) / 8


def test_required_bytes_ignores_everything_but_schema_and_answer():
    q = spec("SUM", "SessionTime", atoms=[("City", "=", "city000")])
    a = widths.required_bytes(CONVIVA, q, ("URL",), 1234)
    assert a == widths.required_bytes(dict(CONVIVA), q, ("URL",), 1234)
    assert widths.required_bytes(CONVIVA, q, ("URL",), 2468) == 2 * a


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_rounding_of_the_control():
    x = np.array([1.0, 1 + 2 ** -10, 7123457.0, 0.1])
    assert np.array_equal(reference.round_to(x, "f64"), x)
    bf = reference.round_to(x, "bf16")
    assert bf[0] == 1.0 and bf[1] == 1.0          # 8-bit mantissa
    assert abs(bf[2] - x[2]) / x[2] > 1e-4
    hi = reference.round_to(x, "high")
    assert hi[1] == x[1]                           # two bf16 parts hold it
    assert 0 < abs(hi[2] - x[2]) / x[2] < 2 ** -15


@pytest.mark.parametrize("intervals,want", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),
    ([(0, 4), (1, 2)], [(0, 4)]),
    ([], []),
])
def test_union(intervals, want):
    assert devtrace.union(intervals) == want


def test_holes_and_attribution():
    busy = devtrace.union([(10, 18), (30, 35)])
    gaps = devtrace.holes(busy, 0, 50)
    assert gaps == [(0, 10), (18, 30), (35, 50)]
    # spans on the harness clock (s); offset puts t=0 s at 0 ns
    spans = [(0.0, 40e-9, "request"), (22e-9, 28e-9, "plan.pilot")]
    named = devtrace.attribute(gaps, spans, 0.0, top=2)
    assert named == [("waiting for arrivals", pytest.approx(15e-9)),
                     ("plan", pytest.approx(12e-9))]
