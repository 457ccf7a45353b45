"""One run of one cell: set-up, window, memory, reference, result line."""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

from chipbench import serve, train
from chipbench.bench import (Cell, Check, arch_module, check, log,
                             memory_peak, per_layer_metrics, result_line)
from chipbench.peaks import peak


@dataclasses.dataclass
class Measured:
    """A cell's window and what its check needs, before the check."""
    cell: Cell
    seed: int
    record: object                 # ServeRecord | TrainRecord
    e2e: Dict[str, float]
    attempted: int
    failed: int
    compiles: int                  # programs lowered inside the window
    memory_peak_bytes: int
    state: Dict                    # serve: params; train: model, readings


def measure(cell: Cell, devs, seed: int, seconds: float, trace_dir=None,
            t_start: Optional[float] = None) -> Measured:
    """Set up, run the window, read the peak memory, free the program."""
    t_start = time.perf_counter() if t_start is None else t_start
    kind = cell.config["kind"]
    if kind == "serve":
        engine, params = serve.setup(cell, seed)
        rec, compiles = serve.window(cell, engine, seed, seconds, trace_dir)
        e2e = serve.end_to_end(rec)
        attempted, failed = serve.attempted_failed(rec)
        mem = memory_peak(devs)
        serve.free(engine)
        state = {"params": params}
    elif kind == "train":
        trainer, steps, readings = train.setup(cell, seed)
        rec, compiles = train.window(cell, trainer, steps, seconds, trace_dir)
        e2e = {"train_tokens_per_s": rec.tokens / rec.window_s}
        attempted, failed = rec.steps, 0
        mem = memory_peak(devs)
        state = {"model": trainer.model, "readings": readings}
        train.free(trainer)
    else:
        raise ValueError(f"unknown cell kind {kind!r}")
    e2e["setup_s"] = rec.t0 - t_start
    return Measured(cell, seed, rec, e2e, attempted, failed, compiles, mem,
                    state)


def compared(m: Measured, control: bool = False) -> tuple:
    """(the numbers compared with the reference, the same numbers for the
    fp8 control put in the program's place, or None)."""
    cell, arch = m.cell, arch_module(m.cell.config)
    if cell.config["kind"] == "serve":
        picks = serve.sample(m.record, m.seed)
        if not picks:
            return {"logit_gap": float("inf")}, None
        log(f"check: {len(picks)} requests, "
            f"{sum(len(r.tokens) for r in picks)} served tokens compared")
        g, c = serve.logit_gaps(arch, m.state["params"], cell.config, picks,
                                control=control)
        return {"logit_gap": g}, ({"logit_gap": c} if control else None)
    readings, model = m.state["readings"], m.state["model"]
    ref = train.reference(arch, cell, model, m.seed, readings["batches"])
    log(f"check: losses {readings['losses']} vs reference {ref['losses']}")
    ctrl = None
    if control:
        low = train.reference(arch, cell, model, m.seed, readings["batches"],
                              low=True)
        log(f"check: fp8 control losses {low['losses']}")
        ctrl = train.gaps(low, ref)
    return train.gaps(readings, ref), ctrl


def run(cell: Cell, devs, *, seed: int, seconds: float, trace_dir=None,
        t_start: Optional[float] = None,
        reduce_trace: Optional[Callable] = None, out=print) -> int:
    m = measure(cell, devs, seed, seconds, trace_dir, t_start)
    summary = reduce_trace(trace_dir) if trace_dir is not None else None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": m.memory_peak_bytes}
    values, _ = compared(m)
    checks: List[Check] = [check(cell, k, v) for k, v in values.items()]
    if summary is None:
        metrics = {e["name"]: {"value": m.e2e[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
        breakdown = None
    else:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        m.record.trace = summary
        m.record.peak = peak(devs[0].device_kind)
        metrics = per_layer_metrics(cell, m.record)
        breakdown = summary.breakdown()
    late = (f"; generator at most {serve.lateness_ms(m.record):.1f} ms late"
            if cell.mix["kind"] == "open_loop" else "")
    if cell.config["kind"] == "serve":
        late += "; " + serve.host_stalls(m.record)
    log(f"setup_s {m.e2e['setup_s']:.3f}; window {m.record.window_s:.3f} s;"
        f" programs lowered in the window {m.compiles}{late}")
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) -> "
            f"{'ok' if c.ok else 'FAIL'}")
    out(result_line(correct=all(c.ok for c in checks),
                    attempted=m.attempted, failed=m.failed, metrics=metrics,
                    device=device, checks=checks, breakdown=breakdown))
    return 0
