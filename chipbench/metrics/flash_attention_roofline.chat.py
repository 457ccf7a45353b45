"""The flash attention kernel's time in the traced window's prefills
against the least time the chip needs for their causal attention.  The
program runs the kernel only for its longer prompts (shorter ones take
a dense jnp path), so the calls, one per layer per prefill, are matched
to the longest prefills of the window."""
from chipbench import flops
from chipbench.readers import PREFILL_PROGRAM, prefills_in_window, share


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace.kernel(PREFILL_PROGRAM)
    L = run.config["num_hidden_layers"]
    longest = sorted(prefills_in_window(run), reverse=True)
    need = sum(L * flops.roofline_seconds(
        *flops.flash_attention_call(run.config, s), run.peak)
        for s in longest[:round(calls / L)])
    return share(need, spent)
