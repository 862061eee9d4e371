"""The span readers of the per-layer metrics, on a hand-built run: traces
with known spans, and a device trace with known idle gaps."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import devtrace, harness  # noqa: E402


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", name).read


def trace(t0, t1, spans):
    """A trace from (name, parent, start, end); index 0 is the root."""
    recs = [SimpleNamespace(index=i, name=n, parent=p, t0=a, t1=b)
            for i, (n, p, a, b) in enumerate(spans)]
    return SimpleNamespace(t0=t0, t1=t1, total_s=t1 - t0, spans=recs)


def record(tr, done, answered=True):
    answer = SimpleNamespace(trace=tr) if answered else None
    return harness.Record(spec=None, due=0.0, done=done, answer=answer)


# A batch of one: queued 1 s, held 0.5 s, planned with a probe scan, one
# final scan, the answer fetched; 1.5 s of it under no span but the root.
A = trace(100.0, 110.0, [
    ("request", -1, 100.0, 110.0),
    ("parse", 0, 100.0, 100.5),
    ("admit", 0, 100.5, 102.0),
    ("admit.queue", 2, 100.5, 101.5),
    ("plan", 0, 102.0, 104.0),
    ("scan", 4, 102.5, 103.5),
    ("scan.launch", 5, 102.5, 102.75),
    ("scan.wait", 5, 102.75, 103.5),
    ("scan", 0, 105.0, 107.0),
    ("scan.launch", 8, 105.0, 105.5),
    ("scan.wait", 8, 105.5, 107.0),
    ("fetch", 0, 107.5, 108.5),
])
# Served by one scan; nothing queued.
B = trace(104.0, 108.0, [
    ("request", -1, 104.0, 108.0),
    ("admit", 0, 104.0, 105.0),
    ("admit.queue", 1, 104.0, 104.0),
    ("scan", 0, 105.0, 107.0),
    ("scan.launch", 3, 105.0, 105.5),
    ("scan.wait", 3, 105.5, 107.0),
])


def run_view(records, reduced=None, traced=None):
    return harness.RunView(records, (100.0, 150.0), {}, reduced, traced, {},
                           "TPU v5 lite")


@pytest.mark.parametrize("metric,want", [
    ("scan_launch_ms_per_query", (250 + 500 + 500) / 2),
    ("scan_wait_ms_per_query", (750 + 1500 + 1500) / 2),
    ("scan_waits_per_query", (2 + 1) / 2),
    ("queue_wait_ms_per_query", (1000 + 0) / 2),
    # A: 10 s less [100, 104] U [105, 107] U [107.5, 108.5] = 3 s.
    # B: 4 s less [104, 105] U [105, 107] = 1 s.
    ("unattributed_ms_per_query", (3000 + 1000) / 2),
])
def test_span_readers_average_over_answered_queries(metric, want):
    # An unanswered query and an answer without a trace read nothing.
    view = run_view([record(A, 110.0), record(B, 108.0),
                     record(None, 109.0), record(A, None, answered=False)])
    assert reader(metric)(view) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "scan_launch_ms_per_query", "scan_wait_ms_per_query",
    "scan_waits_per_query", "queue_wait_ms_per_query"])
def test_span_readers_read_nothing_without_their_spans(metric):
    """A program that records no such span (the parent of the scan split)
    reads None, not 0, and does not raise."""
    old = trace(100.0, 104.0, [("request", -1, 100.0, 104.0),
                               ("admit", 0, 100.0, 101.0),
                               ("scan", 0, 101.0, 103.0)])
    assert reader(metric)(run_view([record(old, 104.0)])) is None
    assert reader(metric)(run_view([])) is None


def test_scan_device_idle_maps_spans_by_the_window_mark():
    # Traced part 100..110 s on the harness clock, its start mark at
    # profiler 5e9 ns: a span at t s sits at t * 1e9 + (5e9 - 100e9) ns.
    off = 5e9 - 100e9
    gaps = [(102.6e9 + off, 102.85e9 + off),    # across A's probe launch
            (103.0e9 + off, 104.0e9 + off),     # half in A's probe wait
            (104.2e9 + off, 104.8e9 + off),     # between A's scans
            (106.0e9 + off, 108.0e9 + off)]     # in A's and B's final wait
    reduced = devtrace.Reduced((5e9, 15e9), 5e9, 0.0, {}, gaps)
    view = run_view([record(A, 109.0), record(B, 108.0),
                     record(A, 111.0)],         # finished after the trace
                    reduced, (100.0, 110.0))
    read = reader("scan_device_idle_ms_per_query")
    # A: 0.25 + 0.5 + 1.0 s; B: 1.0 s.
    assert read(view) == pytest.approx((1750 + 1000) / 2)
    assert read(run_view(view.records)) is None          # no device trace


def test_overlap_of_interval_lists():
    from blinkbench.spans import overlap
    assert overlap([(0, 2), (5, 9)], [(1, 6), (8, 20)]) == 1 + 1 + 1
    assert overlap([], [(0, 1)]) == 0.0


@pytest.fixture
def quiet_cache(tmp_path, monkeypatch):
    """The run turns JAX's persistent cache on: point it at a temporary
    directory and put the process's settings back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


NEW = ("scan_launch_ms_per_query", "scan_wait_ms_per_query",
       "scan_waits_per_query", "scan_device_idle_ms_per_query",
       "queue_wait_ms_per_query", "unattributed_ms_per_query")


def test_traced_run_reports_every_span_metric(quiet_cache, capsys):
    """A `--trace 1` run of a cell, on the CPU at a small size, reports
    each span metric the cell lists (device time on the CPU means
    nothing; here it only has to be read)."""
    import json
    sys.path.insert(0, str(harness.ROOT / "src"))
    rc = harness.main(["--workload", "tpch.range_open", "--seed",
                       str(2 ** 31 + 7), "--seconds", "3", "--trace", "1"],
                      require_tpu=False, n_rows=40_000,
                      engine_overrides={"k1": 800.0, "use_pallas": False})
    assert rc == 0
    out = capsys.readouterr()
    metrics = json.loads(out.out.strip().splitlines()[-1])["metrics"]
    for name in NEW:
        assert metrics[f"{name}.bound_met"]["value"] >= 0.0, out.err[-2000:]
    assert metrics["scan_waits_per_query.bound_met"]["value"] >= 1.0
