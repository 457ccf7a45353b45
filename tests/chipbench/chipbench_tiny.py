"""Tiny copies of the benchmark's cells for CPU tests: the same harness,
configuration layout and limits, at widths the CPU runs in seconds."""
from __future__ import annotations

import copy

from chipbench.bench import Cell, find_cell

TINY_SIZES = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_hidden_layers=2, vocab_size=512)


# Limits that differ at the tiny size, from its readings on the CPU over
# ten seeds: the change after three steps reads 0.0004-0.0022 for the
# program and 0.0039-0.0107 for the fp8 control (the cell's own limit,
# set at its size on the chip, would fail sound tiny runs).  The
# tensor-parallel cell on four CPU devices reads at most 0.0019 (first
# gradient) and 0.0020 (change) for the program, at least 0.0093 and
# 0.0021 for the control.
TINY_LIMITS = {"qwen3-32b-train.pack4k": {"change_norm_gap": 0.003},
               "qwen3-32b-train-tp4.pack4k": {"grad_norm_gap": 0.004,
                                              "change_norm_gap": 0.003}}

# Heads that the tensor-parallel cell's four chips divide, as its own
# 64 query and 8 kv heads do.
TINY_OVERRIDES = {"qwen3-32b-train-tp4.pack4k": dict(num_attention_heads=8,
                                                     num_key_value_heads=4)}


def tiny_cell(name: str) -> Cell:
    cell = copy.deepcopy(find_cell(name))
    cell.config.update(TINY_SIZES, **TINY_OVERRIDES.get(name, {}))
    for k, v in TINY_LIMITS.get(name, {}).items():
        cell.limits[k]["limit"] = v
    if cell.config["kind"] == "serve":
        cell.config["serve"].update(kv_pool_tokens=8 * 64, max_slots=4,
                                    block_size=16, prefill_chunk=32,
                                    prefill_len=64)
        cell.mix["prompt"].update(min=4, max=32)
        cell.mix["output"].update(min=2, max=24)
        if cell.mix["kind"] == "open_loop":
            cell.mix["prompt"]["median"] = 12
            cell.mix["output"]["median"] = 8
            cell.mix["rate_per_s"] = 6.0
        else:
            cell.mix["clients"] = 4
    else:
        cell.mix["seq_len"] = 64
    return cell
