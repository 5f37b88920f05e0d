"""attend_roofline: the least time the attend calls' required work takes
at the chip's peak (harness.work: the bytes the CRAM layout must move,
the attention operations), over the device time of the attend program
(its view copy and the decode kernel), in %."""

from harness import work

PROGRAMS = (r"^jit_decode_attention_fused",)


def read(run):
    if run.trace is None:
        return None
    dev_s = run.trace.program_s(list(PROGRAMS))
    if dev_s <= 0:
        return None
    share = run.packed_share()
    least, kinds = 0.0, {"hbm": 0.0, "flops": 0.0}
    for a, b, ctx in run.rec.attends:
        nbytes, flops = work.attend_work(ctx, share, run.geo)
        t, kind = work.bound(nbytes, flops, run.peaks)
        least += t
        kinds[kind] += t
    return 100.0 * least / dev_s, {"bound": max(kinds, key=kinds.get)}
