"""run.py refuses to measure without the chip, and without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def run(root: Path, workload: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, *BENCHMARK["command"][1:], "--workload", workload,
         "--seed", str(2 ** 31 + 3), "--seconds", "2", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def no_result(p: subprocess.CompletedProcess) -> bool:
    lines = p.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


@pytest.mark.parametrize("workload", [w["name"]
                                      for w in BENCHMARK["workloads"]])
def test_no_accelerator_no_result(workload):
    p = run(ROOT, workload)
    assert p.returncode != 0
    assert no_result(p)
    assert "TPU" in p.stderr


def test_benchmark_alone_is_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in BENCHMARK["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, BENCHMARK["workloads"][0]["name"])
    assert p.returncode != 0
    assert no_result(p)


def test_command_and_paths_stay_inside_the_benchmark():
    assert BENCHMARK["command"][0] == "python3"
    for word in BENCHMARK["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in BENCHMARK["paths"])
    for c in BENCHMARK["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCHMARK["paths"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCHMARK["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    """Each per-layer metric's reader, found as the harness finds it, and
    every cell it names reports the end-to-end metric it moves."""
    sys.path.insert(0, str(BENCH))
    from blinkbench import harness
    m = next(x for x in BENCHMARK["per_layer"] if x["name"] == metric)
    base = metric.split(".")[0]
    reader = harness.load_module(BENCH / "metrics" / f"{base}.py", base)
    assert callable(reader.read)
    for cell in m["workloads"]:
        loaded = harness.load_cell(ROOT, cell)
        assert m["moves"] in {e["name"] for e in loaded.end_to_end}
