"""Model FLOPs per step (6 per weight per token, causal attention,
no recompute) times steps over the window, chips and the bf16 peak."""
from chipbench import flops
from chipbench.readers import share


def read(run):
    work = run.steps * flops.train_flops(run.config, run.batch, run.seq)
    return share(work / run.peak["bf16_flops_per_s"] / run.chips,
                 run.window_s)
