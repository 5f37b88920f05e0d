"""Bulk chunked-prefill packing: compress a whole prompt in ONE launch.

The decode path packs incrementally — O(new groups) per token via
`layout_window` over the dirty columns.  Prefill is the bulk-transfer
dual: a T-token prompt lands as one scatter and every page group it
touches is codec-tried, marker-framed, and slot-placed in a single
vmapped pallas_call (the same registry codecs as the incremental path:
pair int8-delta, quad int4-delta).  A partial tail page arrives
zero-padded in its group and simply fails the fit check, staying raw —
exactly what the token-by-token replay would converge to, which is what
makes the fused path bit-identical to the append oracle.

`prefill_pack` is the kernel-layer entry; `SlotKVCache._prefill` fuses
it with the prompt scatter, traffic booking, and §VI counter update in
one donated dispatch (pinned by the `serve_prefill` jaxpr-audit golden:
one pallas_call, donation, zero host callbacks).
"""

from __future__ import annotations

import functools

import jax

from .ops import layout_window


@functools.partial(
    jax.jit, static_argnames=("lanes", "page", "use_pack", "interpret"))
def prefill_pack(pages, idx, marker_lanes, enabled, *, lanes, page,
                 use_pack=True, interpret=None):
    """Pack every touched page group of a freshly scattered prompt at once.

    pages:        (B, max_tokens, Hkv, D2) int16 logical page buffer AFTER
                  the prompt rows were scattered in (token-major;
                  max_tokens = n_groups * lanes * page)
    idx:          (W,) int32 touched group columns — the prompt's page run,
                  padded to a power of two by the caller (pad repeats a
                  real column, so relaying it is idempotent)
    marker_lanes: (n_groups, MARKER_LANES) int16 in-band marker words
    enabled:      (B,) bool §VI gate per slot

    Returns `(slots_w, over_w, strips_w, lay, fit)` for the W touched
    columns, same contract as `layout_window`: fitness measured regardless
    of the gate, layout honors it, markers written in-band only where laid.
    """
    b, max_tokens, hkv, d2 = pages.shape
    n_groups = max_tokens // (lanes * page)
    groups = pages.reshape(b, n_groups, lanes, page, hkv, d2)
    win = groups[:, idx]
    return layout_window(win, marker_lanes[idx], enabled,
                         use_pack=use_pack, interpret=interpret)
