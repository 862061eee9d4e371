"""The trace reduction on a trace recorded on the chip (a TPU v5e serving
conviva.mixed_open at 50M rows, 5 s traced), committed beside this file."""
import gzip
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from blinkbench import devtrace, kernels  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "conviva_mixed_open_50m.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.reduce(path)


def test_window_busy_and_kernel_time(reduced):
    assert reduced.window_s == pytest.approx(4.968734907, rel=1e-9)
    assert reduced.busy_s == pytest.approx(0.86696874, rel=1e-6)
    assert reduced.kernel_ns(kernels.SCAN_KERNELS) * 1e-9 == \
        pytest.approx(0.774894794, rel=1e-6)
    assert 0 < reduced.busy_s < reduced.window_s


def test_op_names_and_gaps(reduced):
    top = max(reduced.op_ns, key=reduced.op_ns.get)
    assert top == "vmap_jit_agg_scan_fused_pallas__.2"
    assert all(" = " not in n for n in reduced.op_ns)
    idle = sum(e - s for s, e in reduced.gaps) * 1e-9
    assert idle + reduced.busy_s == pytest.approx(reduced.window_s, rel=1e-9)
    longest = max(e - s for s, e in reduced.gaps) * 1e-9
    assert longest == pytest.approx(0.415191707, rel=1e-6)
