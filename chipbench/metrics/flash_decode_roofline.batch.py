"""The flash-decode kernel's time in the traced window (contiguous KV
rows) against the least time the chip needs to read each live row's K/V,
q and o once per tick and layer (bound by HBM bandwidth)."""
from chipbench import flops
from chipbench.readers import DECODE_PROGRAM, share


def read(run):
    if run.trace is None:
        return None
    spent, _ = run.trace.kernel(DECODE_PROGRAM)
    cfg, L = run.config, run.config["num_hidden_layers"]
    need = 0.0
    for t, rows, keys in run.ticks:
        if run.t0 <= t < run.t1 and rows:
            f, b = flops.flash_decode_call(cfg, [keys / rows] * rows)
            need += L * flops.roofline_seconds(f, b, run.peak)
    return share(need, spent)
