"""Arithmetic the per-layer metric readers share.

A reader (``chipbench/metrics/<metric>.py``) defines ``read(run)`` and
returns the metric, or None where the run holds nothing to read: no
trace, no such kernel in it, no such request.  It never returns 0 for a
share of a roofline or of a peak.
"""
from __future__ import annotations

from chipbench import flops
from chipbench.bench import percentile

# Programs as the trace names them.  A Pallas kernel shows only as a
# ``tpu_custom_call`` op, so a kernel is found by the program it runs in:
# the decode tick holds only flash_decode, a prefill and a train step
# only flash_attention.
DECODE_PROGRAM = r"generate_step"
PREFILL_PROGRAM = r"prefill"
TRAIN_PROGRAM = r"train_step"


def idle_share(run):
    """Share of the traced window in which no operation ran on a chip."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def prefills_in_window(run):
    """Prompt tokens of each prefill that started in the window."""
    return [m.prefilled_tokens for m in run.program.values()
            if run.in_window(m.t_prefill_start) and m.prefilled_tokens]


def served_model_flops(run) -> float:
    """Model FLOPs of the prefills and decoded tokens in the window."""
    cfg = run.config
    total = sum(flops.prefill_flops(cfg, s) for s in prefills_in_window(run))
    for r in run.requests:
        S = len(r.prompt)
        for k, t in enumerate(r.stamps[1:], start=1):
            if run.t0 <= t < run.t1:
                total += flops.decode_flops(cfg, S + k - 1)
    return total


def queue_wait_p90_ms(run):
    waits = [m.queue_wait for m in run.program.values()
             if run.in_window(m.t_submit) and m.queue_wait is not None]
    return percentile(waits, 90) * 1e3 if waits else None


def share(needed_s: float, spent_s: float):
    """needed over spent in %, or None where either is nothing."""
    if not (needed_s > 0 and spent_s > 0):
        return None
    return 100.0 * needed_s / spent_s
