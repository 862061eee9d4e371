"""The reader of `kernel_rows_visited_per_query`, on a hand-built run: the
`rows_visited` attribute of each answer's `scan.launch` spans."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import harness  # noqa: E402


def launches(*rows):
    """A trace with one scan per entry of `rows`; None leaves the launch
    without the attribute, as a program that does not count it does."""
    spans = [SimpleNamespace(name="request", attrs={})]
    for r in rows:
        attrs = {"strata": 200} if r is None else {"strata": 200,
                                                   "rows_visited": r}
        spans += [SimpleNamespace(name="scan", attrs={"rows_read": 1}),
                  SimpleNamespace(name="scan.launch", attrs=attrs),
                  SimpleNamespace(name="scan.wait", attrs={})]
    return SimpleNamespace(spans=spans)


def record(tr, done, answered=True):
    answer = SimpleNamespace(trace=tr) if answered else None
    return harness.Record(spec=None, due=0.0, done=done, answer=answer)


def run_view(records):
    return harness.RunView(records, (100.0, 150.0), {}, None, None, {},
                           "TPU v5 lite")


def test_rows_visited_reader():
    """Sums each answer's `scan.launch` `rows_visited` and averages over the
    answered queries with a trace; a program whose spans carry no such
    attribute reads None."""
    read = harness.load_module(
        BENCH / "metrics" / "kernel_rows_visited_per_query.py",
        "kernel_rows_visited_per_query").read
    view = run_view([record(launches(2048, 10240), 110.0),
                     record(launches(4096), 108.0),
                     record(None, 109.0),
                     record(launches(1 << 20), None, answered=False)])
    assert read(view) == pytest.approx((2048 + 10240 + 4096) / 2)
    assert read(run_view([record(launches(None, None), 104.0)])) is None
    assert read(run_view([])) is None


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


def test_traced_run_reports_rows_visited(quiet_cache, capsys):
    """A `--trace 1` run of tpch, on the CPU at a small size, puts
    `kernel_rows_visited_per_query.bound_met` in its result line."""
    import json
    sys.path.insert(0, str(harness.ROOT / "src"))
    rc = harness.main(["--workload", "tpch.range_open", "--seed",
                       str(2 ** 31 + 11), "--seconds", "3", "--trace", "1"],
                      require_tpu=False, n_rows=40_000,
                      engine_overrides={"k1": 800.0, "use_pallas": False})
    assert rc == 0
    out = capsys.readouterr()
    metrics = json.loads(out.out.strip().splitlines()[-1])["metrics"]
    assert metrics["kernel_rows_visited_per_query.bound_met"]["value"] > 0.0
