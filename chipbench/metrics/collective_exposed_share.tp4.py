"""Share of the traced window in which a collective ran on a chip and no
other operation did, the mean over the cell's chips: the communication
of tensor parallelism that the step does not hide behind compute.  None
where the trace holds no collective."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or t.collective_s <= 0:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
