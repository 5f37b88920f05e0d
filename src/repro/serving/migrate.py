"""Incremental live migration of a serving SlotKVCache (DESIGN.md §12).

CRAM §VI turns compression on and off *while the memory system keeps
serving traffic*.  This module is the serving embodiment: when the
hot-tier gate flips (AutoTuner observation window, forced override) or
the tuner picks a different packing layout mid-serve, the live cache
converges to the new layout **incrementally** — a bounded budget of
page-group columns per decode step — instead of a stop-the-world
rebuild.

The machinery is deliberately derivational, not stateful:

  * `cache._gate_b` (B,) is the per-slot TARGET gate, frozen between
    observation boundaries (`refresh_gate`) so the fused decode step
    never host-syncs the §VI counter;
  * `cache._applied_b` (B, n_groups) records the gate each group's
    physical layout was last laid under (written by every repack);
  * a group is *pending migration* iff it is inside its slot's active
    prefix and `applied != target` — there is no pending mask to keep
    consistent, so interleaved appends, evicts and wakes can never
    drift it.

`quantum(cache, budget)` marks at most `budget` pending group COLUMNS
dirty; the normal incremental repack then re-lays them under the target
gate in the same fused window dispatch as the step's append — migration
rides the existing dirty-mask machinery (PR 3) and is bit-identical to
a from-scratch rebuild at every intermediate step, because the decode
kernel already reads packed vs raw per group from the in-band marker.
`migrated_upto` exposes the per-slot watermark (leading groups already
at the target layout) that tests and reports read.

Packing changes (pair <-> quad) are STRUCTURAL: group geometry, marker
domain and physical shapes all change.  `switch_packing` rebuilds the
raw layout directly from the packing-independent logical `pages` buffer
in one jitted dispatch (booked as repack write traffic), re-allocates
the geometry-dependent state, and leaves every active group
`applied=False` — the same budgeted quanta then promote the cache to
the new packed layout without ever blocking a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..bandwidth.adapters import kv_repack_device
from ..compression.framing import DOMAIN_PAIR, DOMAIN_QUAD
from ..kernels.ref import MARKER_LANES, marker_to_lanes, slot_markers


@functools.partial(jax.jit, static_argnames=("lanes", "page", "slot_bytes",
                                             "strip_bytes"))
def _raw_relayout(pages, lay0, traffic, *, lanes, page, slot_bytes,
                  strip_bytes):
    """Build the RAW physical layout of a new group geometry straight from
    the logical pages (one dispatch — the structural half of a packing
    switch), booking the active groups' raw re-lay as repack write
    traffic.  `lay0` is a zeros((active_groups,), bool) mask: nothing is
    packed yet; the budgeted migration quanta do the promotion."""
    b, t_max, hkv, d2 = pages.shape
    n = t_max // (lanes * page)
    grouped = pages.reshape(b, n, lanes, page, hkv, d2)
    slots = grouped[:, :, 0]
    over = grouped[:, :, 1] if lanes == 2 else grouped[:, :, 1:]
    strips = jnp.zeros((b, n, hkv, d2 + MARKER_LANES), jnp.int16)
    mask = jnp.zeros((b, n), bool)
    traffic, _ = kv_repack_device(traffic, lay0, lanes=lanes,
                                  slot_bytes=slot_bytes,
                                  strip_bytes=strip_bytes)
    return slots, over, strips, mask, traffic


def active_groups(cache) -> np.ndarray:
    """(B,) int: page-group count of each slot's own active prefix."""
    pages_b = -(-cache.tokens_b // cache.page)
    return (-(-pages_b // cache.group_lanes)).astype(np.int64)


def pending_mask(cache) -> np.ndarray:
    """(B, n_groups) bool: groups whose layout was laid under a gate that
    differs from the slot's target — DERIVED from `_applied_b` vs
    `_gate_b`, never stored, so it cannot drift."""
    g_b = active_groups(cache)
    active = np.arange(cache.n_groups)[None, :] < g_b[:, None]
    return active & (cache._applied_b != cache._gate_b[:, None])


def migrated_upto(cache, slot: int) -> int:
    """Per-slot migration watermark: leading group count already laid
    under the slot's target gate (== slot_groups(slot) when settled)."""
    pend = pending_mask(cache)[slot]
    nz = np.flatnonzero(pend)
    return int(nz[0]) if nz.size else int(active_groups(cache)[slot])


def quantum(cache, budget: int) -> int:
    """Mark at most `budget` pending group COLUMNS dirty; the next repack
    (or the fused megastep this rides inside) re-lays them under the
    target gate.  Returns the number of columns claimed — the per-step
    migration work is bounded, so a step never stalls on a flip."""
    if budget <= 0:
        return 0
    pend = pending_mask(cache)
    cols = np.flatnonzero(pend.any(0))[:budget]
    if cols.size:
        cache._dirty_b[:, cols] = True
    return int(cols.size)


def drain(cache, slot: int | None = None) -> int:
    """Settle the layout now (evict capture, oracles): mark every pending
    migration column — of one slot, or all — dirty and repack, which also
    lays out appends still waiting for a repack.  Returns the migration
    column count drained."""
    pend = pending_mask(cache)
    if slot is not None:
        only = np.zeros_like(pend)
        only[slot] = pend[slot]
        pend = only
    cols = np.flatnonzero(pend.any(0))
    cache._dirty_b[:, cols] = True
    # settle under the FROZEN target — drain converges to the current
    # gate, it is not an observation boundary (a clean cache returns
    # before dispatching anything)
    cache.repack(gate=cache._gate_b)
    return int(cols.size)


def status(cache) -> dict:
    """Migration progress snapshot (serve-loop summaries, benchmarks)."""
    pend = pending_mask(cache)
    return {
        "migrating": bool(pend.any()),
        "pending_groups": int(pend.sum()),
        "pending_columns": int(pend.any(0).sum()),
        "watermarks": [migrated_upto(cache, b) for b in range(cache.batch)],
    }


def switch_packing(cache, packing: str) -> None:
    """Structurally re-geometry the live cache to a new packing layout.

    The logical `pages` buffer is packing-shape-independent, so the swap
    builds the raw layout of the NEW geometry from it in one jitted
    dispatch — no data loss, no pack kernel.  Every active group comes
    out `applied=False`; with the target gate on, they are all pending,
    and the budgeted quanta promote them to packed over the following
    steps (mixed packed/raw is exactly what the in-band-marker kernel
    reads).  §VI bookkeeping: the per-slot counter survives (it is the
    gate's memory, independent of geometry); the predictor and the
    uncounted-fitness mask are geometry-indexed and reset — history is
    not re-counted."""
    assert packing in ("pair", "quad"), packing
    if packing == cache.packing:
        return
    lanes = 2 if packing == "pair" else 4
    assert cache.max_pages % lanes == 0, (
        f"max_pages={cache.max_pages} not divisible by {lanes}-lane groups"
        " — SlotKVCache rounds capacity to 4 pages so both layouts fit")
    b, n_groups = cache.batch, cache.max_pages // lanes
    lay0 = jnp.zeros((int(active_groups(cache).sum()),), bool)
    st = cache.state
    slots, over, strips, mask, traffic = _raw_relayout(
        st["pages"], lay0, st["traffic"], lanes=lanes, page=cache.page,
        slot_bytes=cache.slot_bytes, strip_bytes=cache.strip_bytes)
    domain = DOMAIN_PAIR if packing == "pair" else DOMAIN_QUAD
    markers = slot_markers(n_groups, cache.key, domain=domain)
    cache.packing = packing
    cache.group_lanes = lanes
    cache.n_groups = n_groups
    cache._marker_lanes = jnp.asarray(marker_to_lanes(markers))
    st["slots"], st["slots_overflow"], st["strips"] = slots, over, strips
    st["packed_mask"], st["traffic"] = mask, traffic
    st["markers"] = jnp.asarray(markers.view(np.int32))
    st["predictor"] = jnp.zeros((b, n_groups), bool)
    cache._dirty_b = np.zeros((b, n_groups), bool)
    cache._uncounted_b = np.zeros((b, n_groups), bool)
    cache._applied_b = np.zeros((b, n_groups), bool)
    cache._last_enabled = np.zeros(b, bool)
    # base-class 1-D masks: unused by SlotKVCache but kept shape-true
    cache._dirty = np.zeros(n_groups, bool)
    cache._uncounted = np.zeros(n_groups, bool)


__all__ = ["active_groups", "pending_mask", "migrated_upto", "quantum",
           "drain", "status", "switch_packing"]
