"""Median time the engine spent admitting a request (its ``engine.admit``
span: prefill start to its slot armed, ``RequestMetrics.admit_time``)
over the admissions that began in the traced window.  None for a program
that does not stamp the close of an admission."""
from chipbench.bench import percentile


def read(run):
    if run.trace is None:
        return None
    times = [getattr(m, "admit_time", None) for m in run.program.values()
             if run.in_window(m.t_prefill_start)]
    times = [t for t in times if t is not None]
    return percentile(times, 50) * 1e3 if times else None
