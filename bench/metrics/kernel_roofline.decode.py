"""kernel_roofline: the least time the attend calls' required work takes
at the chip's peak (harness.work, as for attend_roofline), over the
device time of the decode kernel's own ops in the window: the
`pallas_call` named `cram_decode_attention_batched`, without the attend
program's view copy, in %."""

import re

from harness import trace, work

# an op's name in the trace is its HLO instruction, `%<name>.<n> = ...`
KERNEL = re.compile(r"^%?cram_decode_attention_batched(\.\d+)?( |$)")


def kernel_s(tr) -> float:
    """Device seconds of the decode kernel's ops inside the window,
    summed over devices."""
    tot = 0.0
    for plane, line, name, start, dur in tr.events:
        if (line == trace.OP_LINE and plane.startswith(trace.DEVICE_PREFIX)
                and KERNEL.search(name)):
            tot += max(0.0, min(start + dur, tr.t1) - max(start, tr.t0))
    return tot * 1e-9


def read(run):
    if run.trace is None:
        return None
    dev_s = kernel_s(run.trace)
    if dev_s <= 0:
        return None
    share = run.packed_share()
    least, kinds = 0.0, {"hbm": 0.0, "flops": 0.0}
    for _, _, ctx in run.rec.attends:
        nbytes, flops = work.attend_work(ctx, share, run.geo)
        t, kind = work.bound(nbytes, flops, run.peaks)
        least += t
        kinds[kind] += t
    return 100.0 * least / dev_s, {"bound": max(kinds, key=kinds.get)}
