"""The flash attention kernel's time in the traced window against the
least time the chip needs for each of its calls (a causal forward over
the step's sequence; full remat calls it again in the backward)."""
from chipbench import flops
from chipbench.readers import TRAIN_PROGRAM, share


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace.kernel(TRAIN_PROGRAM)
    one = flops.roofline_seconds(
        *flops.flash_attention_call(run.config, run.seq, batch=run.batch),
        run.peak)
    return share(calls * one, spent)
