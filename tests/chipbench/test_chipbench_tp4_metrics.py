"""The tensor-parallel cell's per-layer readers on made-up records."""
from types import SimpleNamespace as NS

import pytest

from chipbench.bench import find_cell, load_reader
from chipbench.peaks import peak

CELL = find_cell("qwen3-32b-train-tp4.pack4k")
EXPOSED = load_reader("collective_exposed_share.tp4")
IDLE = load_reader("device_idle_share.tp4")
MFU = load_reader("mfu.tp4")


def trace(collective_s=0.5, exposed_s=0.25, busy_s=9.0, window_s=10.0):
    return NS(collective_s=collective_s, collective_exposed_s=exposed_s,
              busy_s=busy_s, window_s=window_s)


def run(t=None, chips=4):
    return NS(config=CELL.config, chips=chips, steps=20, batch=1, seq=4096,
              window_s=10.0, peak=peak("TPU v5 lite"), trace=t)


def test_exposed_collectives_are_a_share_of_the_window():
    assert EXPOSED(run(trace())) == pytest.approx(2.5)
    assert IDLE(run(trace())) == pytest.approx(10.0)


@pytest.mark.parametrize("t", [None, trace(collective_s=0.0, exposed_s=0.0),
                               trace(window_s=0.0)])
def test_a_trace_with_nothing_to_read_gives_no_share(t):
    assert EXPOSED(run(t)) is None


def test_mfu_counts_the_train_step_over_the_cells_chips():
    one = MFU(run(chips=1))
    assert MFU(run(chips=4)) == pytest.approx(one / 4)
    assert MFU(run()) == load_reader("mfu.train")(run())
