"""A whole run, minus the look for a chip, at a small size on the CPU: sound
it is correct; with the served path broken underneath it is not.

Faults a cell can have (a single chip serves no exchange between chips and
keeps no training state):
* an answer altered where it is produced (every estimate off by 0.1%);
* half of the scanned rows left out, the totals scaled up from the rest.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import harness  # noqa: E402

ARGS = ["--workload", "tpch.range_open", "--seed", str(2 ** 31 + 5),
        "--seconds", "3", "--trace", "0"]


def altered(est_lib):
    orig = est_lib.estimate

    def estimate(*a, **kw):
        e = orig(*a, **kw)
        return est_lib.Estimate(e.value * 1.001, e.variance, e.n)
    return "estimate", estimate


def half_rows(est_lib):
    orig = est_lib.grouped_moments

    def grouped_moments(values, rates, mask, codes, n_groups):
        import jax.numpy as jnp
        keep = (jnp.arange(mask.shape[-1]) % 2) == 0
        m = orig(values, rates, mask & keep, codes, n_groups)
        return est_lib.GroupedMoments(
            n=m.n, wsum=2 * m.wsum, wxsum=2 * m.wxsum, wx2sum=2 * m.wx2sum,
            var_count=2 * m.var_count, var_sum=2 * m.var_sum,
            var_sum2=2 * m.var_sum2)
    return "grouped_moments", grouped_moments


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


@pytest.mark.parametrize("fault,correct", [(None, True), (altered, False),
                                           (half_rows, False)])
def test_run_is_correct_only_when_sound(fault, correct, quiet_cache,
                                        monkeypatch, capsys):
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core import estimators as est_lib
    if fault is not None:
        monkeypatch.setattr(est_lib, *fault(est_lib))
    rc = harness.main(ARGS, require_tpu=False, n_rows=40_000,
                      engine_overrides={"k1": 800.0, "use_pallas": False})
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is correct, out.err[-2000:]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1
    assert {"bound_met_share", "setup_s"} <= set(result["metrics"]) <= {
        "bound_met_share", "timebound_halfwidth_pct", "setup_s"}
