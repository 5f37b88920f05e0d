"""What the CRAM layout must move and compute for a decode step.

Counted from the layout that holds (the pool's `packed_mask`), not from
what the kernel happens to DMA: one slot plus its strip per packed live
page group, one slot plus its strip per live page of a raw group, plus
the appended KV; the attention operations beside the bytes.  A change
that realises CRAM's saving therefore cannot read over the roofline.
"""

from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class Geometry:
    page: int
    lanes: int
    n_kv: int
    d: int
    hq: int

    @property
    def slot_bytes(self) -> int:        # one page of K|V, bf16
        return self.page * self.n_kv * 2 * self.d * 2

    @property
    def strip_bytes(self) -> int:       # base row + two marker lanes, int16
        return self.n_kv * (2 * self.d + 2) * 2

    @property
    def token_bytes(self) -> int:       # one appended token's K and V, bf16
        return 2 * self.n_kv * self.d * 2


def seq_bytes(tokens: int, packed_share: float, g: Geometry) -> float:
    """Bytes one sequence's attend must read at `tokens` context when a
    share `packed_share` of its complete page groups is packed."""
    pages = math.ceil(tokens / g.page)
    full_groups = pages // g.lanes
    packed = packed_share * full_groups
    raw_pages = pages - packed * g.lanes
    return (packed + raw_pages) * (g.slot_bytes + g.strip_bytes)


def attend_flops(tokens: int, g: Geometry) -> int:
    """QK^T and PV over `tokens` positions for every query head."""
    return 4 * g.hq * g.d * tokens


def attend_work(ctx: list, packed_share: float, g: Geometry):
    """(bytes, flops) of one attend call over sequences at contexts ctx."""
    return (sum(seq_bytes(t, packed_share, g) for t in ctx),
            sum(attend_flops(t, g) for t in ctx))


def bound(nbytes: float, flops: float, peaks: dict):
    """(least seconds, "hbm" | "flops"): the larger of bytes over the
    HBM bandwidth and operations over the bf16 peak."""
    tb = nbytes / peaks["hbm_bytes_per_s"]
    tf = flops / peaks["bf16_flops_per_s"]
    return (tb, "hbm") if tb >= tf else (tf, "flops")
