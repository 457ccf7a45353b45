"""The reducer on a trace recorded on a TPU v5e: a tiny serve cell (two
layers, 1 s window) through the harness with ``--trace 1``."""
import gzip
import pathlib
import shutil

import pytest

from chipbench import tracing

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "tinychat.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tinychat.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.reduce_file(path)


def test_window_and_busy(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(1.001196396, rel=1e-6)
    assert summary.busy_s == pytest.approx(0.02540985, rel=1e-6)


def test_programs_and_the_decode_kernel(summary):
    assert {"jit_generate_step", "jit_prefill"} <= set(summary.module_s)
    secs, calls = summary.kernel("generate_step")
    assert secs == pytest.approx(0.018167674, rel=1e-6) and calls > 0
    assert summary.kernel("train_step") == (0, 0)
    assert summary.collective_s == 0


def test_host_spans_name_the_gaps(summary):
    assert summary.spans["bench.window"] == 1
    assert summary.spans["bench.engine_step"] > 0
    names = {n for n, _ in summary.gaps}
    assert names <= {"bench.engine_step", "bench.wait_arrival",
                     "bench.submit", "no bench span"}
    assert summary.gaps[0][0] == "bench.wait_arrival"
    idle = sum(g for _, g in summary.gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    b = summary.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
