"""Model FLOPs of the prefills in the traced window over the device time
of the prefill programs, as a share of the chip's bf16 peak."""
from chipbench import flops
from chipbench.readers import PREFILL_PROGRAM, prefills_in_window, share


def read(run):
    if run.trace is None:
        return None
    spent, _ = run.trace.modules_matching(PREFILL_PROGRAM)
    work = sum(flops.prefill_flops(run.config, s)
               for s in prefills_in_window(run))
    return share(work / run.peak["bf16_flops_per_s"], spent)
