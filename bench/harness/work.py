"""What the CRAM layout must move and compute for a decode step.

Counted from the layout that holds (the pool's `packed_mask`), not from
what the kernel happens to DMA: one slot plus its strip per packed live
page group, one slot plus its strip per live page of a raw group, plus
the appended tokens; the attention operations beside the bytes.  A
change that realises CRAM's saving therefore cannot read over the
roofline.

The widths are the attention kind's (`bench/kinds/<kind>.py`): its work
object gives `seq_bytes(tokens, packed_share)`, `attend_flops(tokens)`
and `token_bytes`, and the readers reach it as `Run.geo`.
"""

from __future__ import annotations

import math


def paged_bytes(tokens: int, packed_share: float, *, page: int, lanes: int,
                slot_bytes: int) -> float:
    """Bytes one sequence's attend must read at `tokens` context when a
    share `packed_share` of its complete page groups (of `lanes` pages)
    is packed; `slot_bytes` is one slot with its strip."""
    pages = math.ceil(tokens / page)
    full_groups = pages // lanes
    packed = packed_share * full_groups
    raw_pages = pages - packed * lanes
    return (packed + raw_pages) * slot_bytes


def attend_work(ctx: list, packed_share: float, g):
    """(bytes, flops) of one attend call over sequences at contexts ctx."""
    return (sum(g.seq_bytes(t, packed_share) for t in ctx),
            sum(g.attend_flops(t) for t in ctx))


def bound(nbytes: float, flops: float, peaks: dict):
    """(least seconds, "hbm" | "flops"): the larger of bytes over the
    HBM bandwidth and operations over the bf16 peak."""
    tb = nbytes / peaks["hbm_bytes_per_s"]
    tf = flops / peaks["bf16_flops_per_s"]
    return (tb, "hbm") if tb >= tf else (tf, "flops")
