"""step_mfu: the whole decode step's share of the chip's peak: the least
time every step's required work takes (harness.work: the bytes the CRAM
layout must move for the attends, plus the appended KV; the attention
operations beside them), over the traced window, in %.  For decode the
bound is HBM bandwidth."""

from harness import work


def read(run):
    if run.trace is None or not run.rec.attends:
        return None
    share = run.packed_share()
    nbytes = flops = 0.0
    for _, _, ctx in run.rec.attends:
        b, f = work.attend_work(ctx, share, run.geo)
        nbytes += b
        flops += f
    nbytes += run.rec.appended * run.geo.token_bytes
    t, kind = work.bound(nbytes, flops, run.peaks)
    return 100.0 * t / run.trace.window_s, {"bound": kind}
