"""The train cell driven end to end on the CPU at a tiny size: the
harness without its look for a chip.  A sound run is correct, the fp8
control is not, and neither is a step that returns its state unchanged.
(The cell has one row per batch, so no batch can lose half its rows, and
one chip, so no exchange between chips can be left out.)"""
import json

import jax

from chipbench import cells, faults
from chipbench.bench import check
from chipbench_tiny import tiny_cell

SEED = 2**31 + 4243


def test_train_is_correct_and_its_control_is_not():
    cell = tiny_cell("qwen3-32b-train.pack4k")
    m = cells.measure(cell, jax.devices()[:1], SEED, 1.0)
    assert m.attempted > 0 and m.e2e["train_tokens_per_s"] > 0
    values, control = cells.compared(m, control=True)
    assert all(check(cell, k, v).ok for k, v in values.items()), values
    assert not all(check(cell, k, v).ok for k, v in control.items()), control


def test_a_step_that_keeps_its_state_is_caught():
    lines = []
    with faults.kept_state():
        cells.run(tiny_cell("qwen3-32b-train.pack4k"), jax.devices()[:1],
                  seed=SEED, seconds=1.0, out=lines.append)
    out = json.loads(lines[-1])
    assert out["correct"] is False
    assert out["checks"]["change_norm_gap"]["value"] > 0.99
