"""megastep_ms: device time of the fused megastep program (append
scatter, window re-lay, accounting) per ServeLoop.step, from the trace."""

PROGRAMS = (r"^jit__megastep",)


def read(run):
    if run.trace is None:
        return None
    steps = run.trace.spans("bench.step")
    dev_s = run.trace.program_s(list(PROGRAMS))
    if not steps or dev_s <= 0:
        return None
    return dev_s * 1e3 / len(steps)
