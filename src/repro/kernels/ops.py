"""Public jit'd wrappers around the CRAM-KV Pallas kernels.

`build_cram_cache` packs logical KV pages pairwise into physical slots
(raw when the pair doesn't fit), writing base strips + in-band markers;
`build_cram_cache_quad` is the 4:1 analogue over page quads (int4-delta
codec, quad-domain markers).  `pack_window` / `pack_quad_window` /
`raw_window` / `raw_quad_window` are the incremental variants: they
(re)pack only a gathered window of dirty groups, batched over sequences,
so a decode step costs O(new groups) instead of a full rebuild.
`decode_attention_fused` runs the batched 2-D grid kernel
(`cram_decode_attention_batched`) over per-sequence caches and returns
the attention output TOGETHER with the per-sequence (raw, cram)
bytes-moved the kernel measured for exactly the layout it walked —
`decode_attention` / `decode_attention_batched` /
`decode_attention_quad_batched` are thin aliases that drop the bytes.
`hbm_bytes_moved` is the standalone jitted, lanes-aware bandwidth
reduction (same model, incl. the LLP-mispredict re-probe): the kernel
byte output matches it bit-exactly (pinned by tests).  `interpret=None`
resolves through `kernels.resolve_interpret` (interpreted on the CPU
backend only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .bdi_pack import pack_pair, pack_quad
from .cram_attention import (cram_decode_attention,
                             cram_decode_attention_batched)
from ..compression.framing import DEFAULT_MARKER_KEY
from .ref import MARKER_LANES, marker_to_lanes, slot_markers


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_all(pages, markers_i16, *, interpret=None):
    """pages: (2n, page, Hkv, D2) int16 -> (slots, strips, packed_mask)."""
    a, b = pages[0::2], pages[1::2]
    packed, base, ok = pack_pair(a, b, interpret=interpret)
    slots = jnp.where(ok[:, None, None, None], packed, a)
    n, _, hkv, d2 = slots.shape
    strips = jnp.zeros((n, hkv, d2 + MARKER_LANES), jnp.int16)
    strips = strips.at[:, :, :d2].set(base)
    # in-band marker only when actually packed; raw slots keep a zero tail
    tail = jnp.broadcast_to(markers_i16[:, None, :], (n, hkv, MARKER_LANES))
    strips = strips.at[:, :, d2:].set(
        jnp.where(ok[:, None, None], tail, 0))
    return slots, strips, ok


def build_cram_cache(pages, *, key: int = DEFAULT_MARKER_KEY, interpret=None):
    """Pack logical pages (2n, page, Hkv, D2) int16 into a CRAM cache.

    Returns dict(slots, strips, markers (int32), packed_mask, pages_valid):
    for raw pairs, the odd page is left unpacked and must live in its own
    slot — callers lay pages out so hot pairs are adjacent (the paper's
    restricted mapping).  Here the second page of a non-fitting pair is
    stored raw in the *next* slot, mirroring the uncompressed layout.
    """
    n2, page, hkv, d2 = pages.shape
    assert n2 % 2 == 0
    markers = slot_markers(n2 // 2, key)
    mk_lanes = jnp.asarray(marker_to_lanes(markers))
    slots, strips, ok = _pack_all(pages, mk_lanes, interpret=interpret)
    # raw layout for the non-fitting pairs: two slots, one page each
    raw_b = pages[1::2]
    slots_b = jnp.where(ok[:, None, None, None],
                        jnp.zeros_like(raw_b), raw_b)
    return {
        "slots": slots,
        "slots_overflow": slots_b,      # page B of unpacked pairs
        "strips": strips,
        "markers": jnp.asarray(markers.view(np.int32)),
        "packed_mask": ok,
    }


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_window(a, b, marker_lanes, enabled, *, interpret=None):
    """Incrementally (re)pack a gathered window of dirty page pairs.

    a/b: (B, W, page, Hkv, D2) int16 — pageA/pageB of each dirty pair;
    marker_lanes: (W, MARKER_LANES) int16 per-pair marker lanes (shared
    across the batch); enabled: (B,) bool per-sequence compression gate.

    Pack *fitness* is measured for every pair regardless of the gate (the
    §VI dynamic controller samples fitness even while disabled so it can
    re-enable); the *layout* honors the gate: disabled sequences store the
    raw two-slot layout with zeroed strips, exactly as a full rebuild with
    compression off would.

    Returns (slots, overflow, strips, layout_packed (B, W), fit (B, W)).
    """
    packed, base, fit = pack_pair(a, b, interpret=interpret)
    bsz, w, _, hkv, d2 = a.shape
    lay = fit & enabled[:, None]
    sel = lay[:, :, None, None, None]
    slots = jnp.where(sel, packed, a)
    over = jnp.where(sel, jnp.zeros_like(b), b)
    strips = jnp.zeros((bsz, w, hkv, d2 + MARKER_LANES), jnp.int16)
    strips = strips.at[..., :d2].set(base)
    tail = jnp.broadcast_to(marker_lanes[None, :, None, :],
                            (bsz, w, hkv, MARKER_LANES))
    strips = strips.at[..., d2:].set(jnp.where(lay[:, :, None, None],
                                               tail, 0))
    strips = jnp.where(enabled[:, None, None, None], strips, 0)
    return slots, over, strips, lay, fit


@jax.jit
def raw_window(a, b):
    """Raw layout for a window of pairs — never touches the pack kernel.

    The `policy="off"` path: pageA/pageB land in their own slots, strips
    zeroed, nothing packed and no fitness measured.
    """
    bsz, w = a.shape[:2]
    hkv, d2 = a.shape[-2:]
    strips = jnp.zeros((bsz, w, hkv, d2 + MARKER_LANES), jnp.int16)
    none = jnp.zeros((bsz, w), bool)
    return a, b, strips, none, none


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_quad_window(pages, marker_lanes, enabled, *, interpret=None):
    """Incrementally (re)pack a gathered window of dirty page QUADS.

    pages: (B, W, 4, page, Hkv, D2) int16 — the four lanes of each dirty
    group; marker_lanes: (W, MARKER_LANES) int16 per-group quad-domain
    marker lanes; enabled: (B,) bool per-sequence gate.  Same gate
    semantics as pack_window: fitness measured regardless, layout honors
    the gate, disabled sequences get the raw layout with zeroed strips.

    Returns (slots, overflow (B, W, 3, ...), strips, layout_packed, fit).
    """
    a, b, c, d = (pages[:, :, j] for j in range(4))
    packed, base, fit = pack_quad(a, b, c, d, interpret=interpret)
    bsz, w = a.shape[:2]
    hkv, d2 = a.shape[-2:]
    lay = fit & enabled[:, None]
    sel = lay[:, :, None, None, None]
    slots = jnp.where(sel, packed, a)
    over = jnp.where(lay[:, :, None, None, None, None],
                     jnp.zeros_like(pages[:, :, 1:]), pages[:, :, 1:])
    strips = jnp.zeros((bsz, w, hkv, d2 + MARKER_LANES), jnp.int16)
    strips = strips.at[..., :d2].set(base)
    tail = jnp.broadcast_to(marker_lanes[None, :, None, :],
                            (bsz, w, hkv, MARKER_LANES))
    strips = strips.at[..., d2:].set(jnp.where(lay[:, :, None, None],
                                               tail, 0))
    strips = jnp.where(enabled[:, None, None, None], strips, 0)
    return slots, over, strips, lay, fit


@jax.jit
def raw_quad_window(pages):
    """Raw layout for a window of quads (`policy="off"`): every page in its
    own slot, strips zeroed, no fitness measured."""
    bsz, w = pages.shape[:2]
    hkv, d2 = pages.shape[-2:]
    strips = jnp.zeros((bsz, w, hkv, d2 + MARKER_LANES), jnp.int16)
    none = jnp.zeros((bsz, w), bool)
    return pages[:, :, 0], pages[:, :, 1:], strips, none, none


def layout_window(win, marker_lanes, enabled, *, use_pack, interpret=None):
    """Dispatch one gathered dirty window to the right layout kernel.

    win: (B, W, lanes, page, Hkv, D2) int16 — lanes (2 or 4) selects the
    pair/quad family; `use_pack=False` is the `policy="off"` path (raw
    layout, never launches the pack kernel).  The shared entry for the
    incremental repack and the fused serve megastep — one place owns the
    pack/raw x pair/quad product.  Returns the five window outputs
    (slots, overflow, strips, layout_packed, fit)."""
    lanes = win.shape[2]
    assert lanes in (2, 4), lanes
    if lanes == 2:
        if not use_pack:
            return raw_window(win[:, :, 0], win[:, :, 1])
        return pack_window(win[:, :, 0], win[:, :, 1], marker_lanes,
                           enabled, interpret=interpret)
    if not use_pack:
        return raw_quad_window(win)
    return pack_quad_window(win, marker_lanes, enabled, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_all_quad(pages, markers_i16, *, interpret=None):
    """pages: (4n, page, Hkv, D2) int16 -> (slots, overflow, strips, ok)."""
    a, b, c, d = pages[0::4], pages[1::4], pages[2::4], pages[3::4]
    packed, base, ok = pack_quad(a, b, c, d, interpret=interpret)
    sel = ok[:, None, None, None]
    slots = jnp.where(sel, packed, a)
    over_pages = jnp.stack([b, c, d], axis=1)         # (n, 3, page, ...)
    over = jnp.where(ok[:, None, None, None, None],
                     jnp.zeros_like(over_pages), over_pages)
    n, _, hkv, d2 = slots.shape
    strips = jnp.zeros((n, hkv, d2 + MARKER_LANES), jnp.int16)
    strips = strips.at[:, :, :d2].set(base)
    tail = jnp.broadcast_to(markers_i16[:, None, :], (n, hkv, MARKER_LANES))
    strips = strips.at[:, :, d2:].set(jnp.where(ok[:, None, None], tail, 0))
    return slots, over, strips, ok


def build_cram_cache_quad(pages, *, key: int = DEFAULT_MARKER_KEY, interpret=None):
    """Pack logical pages (4n, page, Hkv, D2) int16 into a quad CRAM cache.

    The 4:1 analogue of build_cram_cache: groups of four consecutive pages
    pack into ONE slot via the int4-delta codec when they fit; non-fitting
    groups store all four pages raw (lead slot + 3 overflow slots).
    Markers come from the quad domain so a slot's pair marker can never
    alias its quad marker.
    """
    from ..compression.framing import DOMAIN_QUAD

    n4 = pages.shape[0]
    assert n4 % 4 == 0
    markers = slot_markers(n4 // 4, key, domain=DOMAIN_QUAD)
    mk_lanes = jnp.asarray(marker_to_lanes(markers))
    slots, over, strips, ok = _pack_all_quad(pages, mk_lanes,
                                             interpret=interpret)
    return {
        "slots": slots,
        "slots_overflow": over,         # (n, 3, page, ...) lanes B/C/D
        "strips": strips,
        "markers": jnp.asarray(markers.view(np.int32)),
        "packed_mask": ok,
    }


def physical_view(cache, valid_per_page):
    """Flatten the cache to the slot list the decode kernel walks.

    Packed pair -> 1 slot holding 2 pages; raw pair -> 2 slots (A, B).
    Returns (slots, strips, markers, valid (n,2)) covering every page.
    """
    slots = cache["slots"]
    over = cache["slots_overflow"]
    strips = cache["strips"]
    markers = cache["markers"]
    ok = cache["packed_mask"]
    n, page, hkv, d2 = slots.shape
    vp = valid_per_page.reshape(n, 2)
    # slot stream: [slot_i, overflow_i] for every pair; overflow slots of
    # packed pairs carry zero valid tokens (masked out).
    all_slots = jnp.stack([slots, over], 1).reshape(2 * n, page, hkv, d2)
    zstrip = jnp.zeros_like(strips)
    all_strips = jnp.stack([strips, zstrip], 1).reshape(
        2 * n, hkv, d2 + MARKER_LANES)
    all_markers = jnp.stack([markers, markers], 1).reshape(2 * n)
    v_packed = jnp.stack([vp[:, 0], vp[:, 1]], 1)          # in slot A
    v_raw_a = jnp.stack([vp[:, 0], jnp.zeros_like(vp[:, 0])], 1)
    v_raw_b = jnp.stack([vp[:, 1], jnp.zeros_like(vp[:, 1])], 1)
    va = jnp.where(ok[:, None], v_packed, v_raw_a)
    vb = jnp.where(ok[:, None], jnp.zeros_like(v_raw_b), v_raw_b)
    valid = jnp.stack([va, vb], 1).reshape(2 * n, 2)
    return all_slots, all_strips, all_markers, valid


def decode_attention(q, cache, valid_per_page, *, interpret=None):
    """q: (B, Hq, D) bf16 over ONE shared cache; returns (B, Hq, D)
    float32.  Thin alias over `decode_attention_fused` (bytes dropped)."""
    out, _, _ = decode_attention_fused(q, cache, valid_per_page,
                                       lanes=2, interpret=interpret)
    return out


def decode_attention_ref(q, cache, valid_per_page):
    """Oracle path (pure jnp) over the same physical cache view."""
    slots, strips, markers, valid = physical_view(cache, valid_per_page)
    valid_flat = valid.reshape(-1)
    fn = lambda qi: _ref.cram_decode_attention_ref(
        qi, slots, strips,
        jnp.asarray(np.asarray(markers).view(np.uint32)), valid_flat)
    return jax.vmap(fn)(q)


def decode_attention_batched(q, cache, valid_per_page, *, interpret=None):
    """Per-sequence decode: q (B, Hq, D), cache leaves carry a leading
    batch axis except `markers` (per-pair values, shared across sequences);
    valid_per_page (B, 2n).  Returns (B, Hq, D) float32.  Thin alias over
    `decode_attention_fused` (bytes dropped)."""
    out, _, _ = decode_attention_fused(q, cache, valid_per_page,
                                       lanes=2, interpret=interpret)
    return out


def decode_attention_ref_batched(q, cache, valid_per_page):
    """Oracle counterpart of decode_attention_batched (pure jnp)."""
    markers_u = jnp.asarray(np.asarray(cache["markers"]).view(np.uint32))

    def one(qi, slots, over, strips, ok, vp):
        c = {"slots": slots, "slots_overflow": over, "strips": strips,
             "markers": cache["markers"], "packed_mask": ok}
        s, st, _, v = physical_view(c, vp)
        mk = jnp.stack([markers_u, markers_u], 1).reshape(-1)
        return _ref.cram_decode_attention_ref(qi, s, st, mk, v.reshape(-1))

    return jax.vmap(one)(q, cache["slots"], cache["slots_overflow"],
                         cache["strips"], cache["packed_mask"],
                         jnp.asarray(valid_per_page))


def physical_view_quad(cache, valid_per_page):
    """Quad analogue of physical_view: flatten to the slot list the decode
    kernel walks.  Packed group -> 1 slot holding 4 pages; raw group -> 4
    slots (lead + 3 overflow).  Returns (slots, strips, markers,
    valid (4n, 4)) covering every page."""
    slots = cache["slots"]                  # (n, page, hkv, d2)
    over = cache["slots_overflow"]          # (n, 3, page, hkv, d2)
    strips = cache["strips"]
    markers = cache["markers"]
    ok = cache["packed_mask"]
    n, page, hkv, d2 = slots.shape
    vp = valid_per_page.reshape(n, 4)
    all_slots = jnp.concatenate([slots[:, None], over], axis=1)
    all_slots = all_slots.reshape(4 * n, page, hkv, d2)
    zstrip = jnp.zeros_like(strips)
    all_strips = jnp.stack([strips, zstrip, zstrip, zstrip], 1).reshape(
        4 * n, hkv, d2 + MARKER_LANES)
    all_markers = jnp.repeat(markers, 4)
    zero = jnp.zeros_like(vp[:, 0])
    # lead slot: all four pages when packed, lane A only when raw
    v_lead_raw = jnp.stack([vp[:, 0], zero, zero, zero], 1)
    v_lead = jnp.where(ok[:, None], vp, v_lead_raw)
    # overflow slot j: lane j+1 when raw, dead when packed
    v_over = [
        jnp.where(ok[:, None],
                  jnp.zeros((n, 4), vp.dtype),
                  jnp.stack([vp[:, j + 1], zero, zero, zero], 1))
        for j in range(3)
    ]
    valid = jnp.stack([v_lead, *v_over], 1).reshape(4 * n, 4)
    return all_slots, all_strips, all_markers, valid


def decode_attention_quad_batched(q, cache, valid_per_page, *,
                                  interpret=None):
    """Per-sequence decode over a quad cache: q (B, Hq, D); cache leaves
    carry a leading batch axis except `markers`; valid_per_page (B, 4n).
    Thin alias over `decode_attention_fused` (bytes dropped)."""
    out, _, _ = decode_attention_fused(q, cache, valid_per_page,
                                       lanes=4, interpret=interpret)
    return out


def decode_attention_quad_ref_batched(q, cache, valid_per_page):
    """Oracle counterpart of decode_attention_quad_batched (pure jnp)."""
    markers_u = jnp.asarray(np.asarray(cache["markers"]).view(np.uint32))

    def one(qi, slots, over, strips, ok, vp):
        c = {"slots": slots, "slots_overflow": over, "strips": strips,
             "markers": cache["markers"], "packed_mask": ok}
        s, st, _, v = physical_view_quad(c, vp)
        mk = jnp.repeat(markers_u, 4)
        return _ref.cram_decode_attention_ref(qi, s, st, mk, v.reshape(-1),
                                              lanes=4)

    return jax.vmap(one)(q, cache["slots"], cache["slots_overflow"],
                         cache["strips"], cache["packed_mask"],
                         jnp.asarray(valid_per_page))


@functools.partial(jax.jit, static_argnames=("lanes", "block_groups",
                                             "interpret"))
def decode_attention_fused(q, cache, valid_per_page, predictor=None, *,
                           lanes: int = 2, block_groups: int | None = None,
                           interpret: bool | None = None):
    """The serve decode step as ONE device program: batched 2-D grid
    attention over the physical slot view + per-sequence bytes-moved.

    q (B, Hq, D); cache leaves carry a leading batch axis (per-sequence
    caches) or none (one shared cache walked by every query row) except
    `markers`, which is always shared; valid_per_page (B?, lanes * n)
    valid tokens per logical page; `predictor` is the (B?, n) predicted
    group packedness (the LLP analog) — None means a perfect predictor
    (no re-probe charge).  Returns (out (B, Hq, D) float32, raw_per_seq
    (B,) int32, cram_per_seq (B,) int32) where the byte columns are
    bit-identical to `hbm_bytes_moved`'s per-sequence totals for the
    same masks — measured by the kernel for the layout it walked, not by
    a second pass over the state.
    """
    pv = physical_view if lanes == 2 else physical_view_quad
    markers = cache["markers"]
    vp = jnp.asarray(valid_per_page)
    pred = cache["packed_mask"] if predictor is None else predictor
    if cache["slots"].ndim == 5:        # per-sequence caches
        def one(slots, over, strips, ok, vpi):
            c = {"slots": slots, "slots_overflow": over, "strips": strips,
                 "markers": markers, "packed_mask": ok}
            s, st, _, v = pv(c, vpi)
            return s, st, v

        s, st, v = jax.vmap(one)(cache["slots"], cache["slots_overflow"],
                                 cache["strips"], cache["packed_mask"], vp)
        mk = (jnp.stack([markers, markers], 1).reshape(-1) if lanes == 2
              else jnp.repeat(markers, lanes))
        out, bts = cram_decode_attention_batched(
            q, s, st, mk, v, pred, lanes=lanes, block_groups=block_groups,
            interpret=interpret)
    else:                               # one shared cache
        s, st, mk, v = pv(cache, vp)
        out, bts = cram_decode_attention_batched(
            q, s, st, mk, v, pred, lanes=lanes, block_groups=block_groups,
            shared_cache=True, interpret=interpret)
    return out, bts[:, 0], bts[:, 1]


@functools.partial(jax.jit, static_argnames=("slot_bytes", "strip_bytes"))
def _bytes_moved(packed_mask, live, predicted, *, slot_bytes, strip_bytes):
    """Jitted reduction over (..., n) pair masks -> (raw, cram) byte totals
    per leading batch element (scalar when unbatched)."""
    any_live = live.any(-1)
    n_live = live.sum(-1)
    raw = (n_live * slot_bytes).sum(-1)
    per_pair = jnp.where(packed_mask, slot_bytes + strip_bytes,
                         n_live * (slot_bytes + strip_bytes))
    # LLP-miss re-probe: a pair whose predicted packedness disagrees with
    # its actual layout costs one extra slot DMA on this access.
    reprobe = jnp.where(predicted != packed_mask, slot_bytes, 0)
    cram = jnp.where(any_live, per_pair + reprobe, 0).sum(-1)
    return raw, cram


def hbm_bytes_moved_device(cache, valid_per_page, predictor=None,
                           lanes: int = 2):
    """`hbm_bytes_moved` without the host sync: returns the per-sequence
    (raw, cram) int32 device arrays (scalars when unbatched), so jitted
    serve paths can fold them into a device accumulator instead of
    round-tripping to python ints every step."""
    slots = cache["slots"]
    page, hkv, d2 = slots.shape[-3:]
    slot_bytes = page * hkv * d2 * 2
    strip_bytes = hkv * (d2 + MARKER_LANES) * 2
    ok = jnp.asarray(cache["packed_mask"])
    v = jnp.asarray(valid_per_page).reshape(ok.shape + (lanes,))
    pred = ok if predictor is None else jnp.asarray(predictor)
    return _bytes_moved(ok, v > 0, pred, slot_bytes=slot_bytes,
                        strip_bytes=strip_bytes)


def hbm_bytes_moved(cache, valid_per_page, predictor=None,
                    lanes: int = 2) -> dict:
    """Bandwidth accounting: bytes a decode step DMAs with/without CRAM.

    raw  : one slot per live page (uncompressed layout, no strips)
    CRAM : packed group -> ONE slot + strip serves all `lanes` pages (the
           paper's one-access-N-lines win); unpacked group -> one slot +
           strip per live page (the strip read is the in-band metadata
           overhead, ~1/page of a slot); a *mispredicted* live group — the
           LLP analog predicted the wrong packedness — costs one extra
           slot DMA (the paper's LLP-miss re-probe).

    `predictor` is the (…, n) predicted packed-mask; None means a perfect
    predictor (no re-probe charge).  `lanes` is the group width (2 for the
    pair layout, 4 for quad).  Leading batch axes are reduced per sequence
    and summed into the scalar totals.
    """
    raw, cram = hbm_bytes_moved_device(cache, valid_per_page, predictor,
                                       lanes)
    raw_i, cram_i = int(raw.sum()), int(cram.sum())
    return {"raw_bytes": raw_i, "cram_bytes": cram_i,
            "raw_per_seq": np.asarray(raw), "cram_per_seq": np.asarray(cram),
            "saving": 1.0 - cram_i / max(raw_i, 1)}
