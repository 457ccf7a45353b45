"""Model FLOPs of every prefill and decoded token in the window over the
window times the chip's bf16 peak."""
from chipbench.readers import served_model_flops, share


def read(run):
    return share(served_model_flops(run) / run.peak["bf16_flops_per_s"]
                 / run.chips, run.window_s)
