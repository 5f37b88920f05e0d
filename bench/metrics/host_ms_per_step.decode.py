"""host_ms_per_step: host time, up to return, of the ServeLoop.step and
ServeLoop.attend calls per step, from the harness's own spans in the
traced window."""

SPANS = ("bench.step", "bench.attend")


def read(run):
    if run.trace is None:
        return None
    steps = run.trace.spans("bench.step")
    if not steps:
        return None
    tot = sum(d for name in SPANS for _, d in run.trace.spans(name))
    return tot * 1e-6 / len(steps)
