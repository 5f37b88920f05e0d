"""Pallas kernel: one-pass compressibility scan of a whole memory image.

Computes, for every 64-byte line of an image, in a single kernel pass:
  * the hybrid FPC+BDI compressed size (header byte included) — the same
    quantity as core/compress.compressed_sizes, which stays the bit-true
    numpy reference (cross-checked in tests/test_compress_scan.py);
  * the implicit-metadata marker classification of the line against its
    slot's marker family (COMP2 / COMP4 / INVALID / MAYBE_INVERTED /
    UNCOMP, same enum as core/marker.LineStatus).

This is the sweep-side replacement for looping compress.compressed_sizes +
marker.classify_line over an image line by line: figure-level benchmarks
(Fig. 4 compressibility CDFs, Table III/IV capacity accounting) call it on
multi-MB images in one dispatch.

All kernel arithmetic is int32 (TPU has no int64): the 8-byte-base BDI
modes emulate 64-bit compares with (hi, lo) word pairs, and the marker PRF
is a multiply-add family that wraps identically in int32 (device) and
uint32 (host reference below).  Markers here are the *device* marker family
(core/marker.py's keyed blake2b is the host path; the protocol — per-slot
values, regenerate on LIT overflow — is what matters, not the PRF).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.compression.framing import (DEFAULT_MARKER_KEY, HEADER_BYTES,
                                       IL_MULT, LINE_BYTES, M2_MULT, M4_MULT)
from repro.compression.marker import LineStatus

from . import resolve_interpret

WORDS_PER_LINE = 16
BLOCK_LINES = 256

# multiply-add marker family constants (odd multipliers; wrap mod 2^32) —
# defined once in compression.framing, aliased here for kernel-local use
_M2_MULT = M2_MULT
_M4_MULT = M4_MULT
_IL_MULT = IL_MULT

# BDI modes as (base_bytes, delta_bytes, payload_bytes), evaluated from the
# largest payload to the smallest exactly like core/bdi.bdi_sizes
_BDI_MODES = ((8, 4, 41), (4, 2, 38), (2, 1, 38), (8, 2, 25), (4, 1, 22),
              (8, 1, 17))


# ---------------------------------------------------------------------------
# host-side helpers + numpy reference (uint32 arithmetic, bit-identical)
# ---------------------------------------------------------------------------

def device_markers(slot_idx, key: int = DEFAULT_MARKER_KEY):
    """(m2, m4) uint32 device markers for an array of slot indices."""
    idx = np.asarray(slot_idx, dtype=np.uint64) & np.uint64(0xFFFFFFFF)
    two = (np.uint64(2) * idx + np.uint64(1)) & np.uint64(0xFFFFFFFF)
    k = np.uint64(key & 0xFFFFFFFF)
    m2 = (two * np.uint64(_M2_MULT) + k) & np.uint64(0xFFFFFFFF)
    m4 = (two * np.uint64(_M4_MULT) + k) & np.uint64(0xFFFFFFFF)
    return m2.astype(np.uint32), m4.astype(np.uint32)


def device_il_words(slot_idx, key: int = DEFAULT_MARKER_KEY) -> np.ndarray:
    """(N, 16) uint32 invalid-line (Marker-IL) pattern per slot."""
    idx = np.asarray(slot_idx, dtype=np.uint64)[..., None]
    j = np.arange(WORDS_PER_LINE, dtype=np.uint64)[None, :]
    w = ((idx * np.uint64(WORDS_PER_LINE) + j + np.uint64(1))
         * np.uint64(_IL_MULT) + np.uint64(key & 0xFFFFFFFF))
    return (w & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def classify_image_ref(lines: np.ndarray, key: int = DEFAULT_MARKER_KEY) -> np.ndarray:
    """Numpy reference for the kernel's marker classification.

    lines: (N, 64) uint8, line i living in slot i. Returns (N,) int32 of
    core/marker.LineStatus values, with the same priority order as the
    kernel (COMP2 > COMP4 > INVALID > MAYBE_INVERTED > UNCOMP).
    """
    lines = np.ascontiguousarray(lines, dtype=np.uint8)
    n = lines.shape[0]
    words = lines.view("<u4").reshape(n, WORDS_PER_LINE)
    tail = words[:, -1]
    idx = np.arange(n)
    m2, m4 = device_markers(idx, key)
    il = device_il_words(idx, key)
    is2 = tail == m2
    is4 = tail == m4
    is_il = (words == il).all(axis=1)
    inv = (tail == ~m2) | (tail == ~m4) | (words == ~il).all(axis=1)
    out = np.full(n, int(LineStatus.UNCOMP), dtype=np.int32)
    out[inv] = int(LineStatus.MAYBE_INVERTED)
    out[is_il] = int(LineStatus.INVALID)
    out[is4] = int(LineStatus.COMP4)
    out[is2] = int(LineStatus.COMP2)
    return out


def lines_to_words_i32(lines) -> jnp.ndarray:
    """(N, 64) uint8 -> (N, 16) int32 little-endian word bit patterns."""
    b = jnp.asarray(lines).astype(jnp.uint32)
    w = (b[..., 0::4] | (b[..., 1::4] << 8) | (b[..., 2::4] << 16)
         | (b[..., 3::4] << 24))
    return jax.lax.bitcast_convert_type(w, jnp.int32)


# ---------------------------------------------------------------------------
# in-kernel int32 size + classification math
# ---------------------------------------------------------------------------

def _fpc_bytes_i32(w):
    """FPC compressed size in bytes; w: (B, 16) int32 word bit patterns.

    Same pattern table and zero-run encoding as core/fpc.fpc_size_bits, in
    pure int32 (word-as-signed-int32 == the reference's sign-extended view).
    """
    zero = w == 0
    lo16 = ((w & 0xFFFF) ^ 0x8000) - 0x8000
    hi16 = (((w >> 16) & 0xFFFF) ^ 0x8000) - 0x8000
    b0 = w & 0xFF
    repb = ((b0 == ((w >> 8) & 0xFF)) & (b0 == ((w >> 16) & 0xFF))
            & (b0 == ((w >> 24) & 0xFF)))
    # priority chain (last where wins): raw < half_se8 < pad16 < se16 <
    # repb < se8 < se4 — identical to fpc._classify_nonzero
    bits = jnp.full(w.shape, 32, jnp.int32)
    bits = jnp.where((lo16 >= -128) & (lo16 < 128)
                     & (hi16 >= -128) & (hi16 < 128), 16, bits)
    bits = jnp.where((w & 0xFFFF) == 0, 16, bits)
    bits = jnp.where((w >= -32768) & (w < 32768), 16, bits)
    bits = jnp.where(repb, 8, bits)
    bits = jnp.where((w >= -128) & (w < 128), 8, bits)
    bits = jnp.where((w >= -8) & (w < 8), 4, bits)
    nz_bits = jnp.where(zero, 0, 3 + bits)
    total = nz_bits.sum(axis=-1)

    # zero runs: a run of length L costs ceil(L/8) chunks of (3+3) bits,
    # i.e. one chunk per zero word at offset 0, 8, 16, ... into its run.
    # Counted with static shifts (Mosaic has no cumsum) on 0/1 int32 flags
    # (it cannot concatenate bool vectors): word p opens a chunk iff it is
    # zero and the run holding it started at p - 8k.
    def shifted(x, k):              # x[..., p - k], 0 before the line
        pad = jnp.zeros(x.shape[:-1] + (k,), x.dtype)
        return jnp.concatenate([pad, x[..., :-k]], axis=-1)

    z = zero.astype(jnp.int32)
    starts = z & (1 - shifted(z, 1))
    opens = starts
    run8 = z                        # words p-8k .. p all zero
    for k in range(8, WORDS_PER_LINE, 8):
        for s in range(k - 7, k + 1):
            run8 = run8 & shifted(z, s)
        opens = opens | (run8 & shifted(starts, k))
    chunks = opens.sum(axis=-1)
    return (total + chunks * 6 + 7) // 8


_SIGN = -(1 << 31)  # 0x80000000 bit pattern (python int: stays weakly typed)


def _as_i32(u: int) -> int:
    """uint32 constant -> equivalent int32 python int (avoids traced consts
    inside the kernel: pallas requires captured values to be inline scalars)."""
    return u - (1 << 32) if u >= (1 << 31) else u


_M2_I32, _M4_I32, _IL_I32 = _as_i32(_M2_MULT), _as_i32(_M4_MULT), _as_i32(_IL_MULT)


def _fits_i32(v, d):
    """Does int32 v fit in a signed d-byte integer (d in 1, 2, 4)?"""
    if d == 4:
        return jnp.full(v.shape, True)
    lim = 1 << (8 * d - 1)
    return (v >= -lim) & (v < lim)


def _fits_i64(hi, lo, d):
    """Does the 64-bit (hi, lo) int32 pair fit in a signed d-byte integer?"""
    ok32 = hi == (lo >> 31)          # value fits in 32 bits at all
    return ok32 & _fits_i32(lo, d)


def _ult(a, b):
    """Unsigned < on int32 bit patterns (for the 64-bit borrow)."""
    return (a ^ _SIGN) < (b ^ _SIGN)


def _pick(sel, e):
    """Row-wise gather e[i, sel[i]] as a select-sum (TPU-friendly)."""
    ids = jax.lax.broadcasted_iota(jnp.int32, e.shape, len(e.shape) - 1)
    return jnp.where(ids == sel[..., None], e, 0).sum(axis=-1)


def _first_true(x):
    """Index of the first True along the last axis (its length if none):
    argmax as a masked min, which Mosaic lowers."""
    ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, len(x.shape) - 1)
    return jnp.where(x, ids, x.shape[-1]).min(axis=-1)


def _bdi_fits_small(e, wrap_bits, d):
    """fits for b<=4 modes; e: (B, k) int32 elements (sign-extended)."""
    imm = _fits_i32(e, d)
    nonimm = ~imm
    any_non = nonimm.any(axis=-1)
    fi = _first_true(nonimm)
    base = jnp.where(any_non, _pick(fi, e), 0)
    delta = e - base[..., None]
    if wrap_bits < 32:                     # wrap into the element width
        m = (1 << wrap_bits) - 1
        delta = ((delta & m) ^ (1 << (wrap_bits - 1))) - (1 << (wrap_bits - 1))
    return (imm | _fits_i32(delta, d)).all(axis=-1)


def _bdi_fits_b8(lo, hi, d):
    """fits for base-8 modes; lo/hi: (B, 8) int32 halves of 64-bit elems."""
    imm = _fits_i64(hi, lo, d)
    nonimm = ~imm
    any_non = nonimm.any(axis=-1)
    fi = _first_true(nonimm)
    blo = jnp.where(any_non, _pick(fi, lo), 0)
    bhi = jnp.where(any_non, _pick(fi, hi), 0)
    dlo = lo - blo[..., None]
    borrow = _ult(lo, blo[..., None]).astype(jnp.int32)
    dhi = hi - bhi[..., None] - borrow
    return (imm | _fits_i64(dhi, dlo, d)).all(axis=-1)


def _bdi_bytes_i32(w, w_eo):
    """Best BDI payload size; w: (B, 16) int32, w_eo: the same words with
    the even ones first (the low and high halves of the 8-byte elements:
    a lane-strided slice is a gather Mosaic cannot lower).  Mirrors
    bdi.bdi_sizes."""
    e4 = w                                                    # (B, 16)
    lo16 = ((w & 0xFFFF) ^ 0x8000) - 0x8000
    hi16 = (((w >> 16) & 0xFFFF) ^ 0x8000) - 0x8000
    e2 = jnp.stack([lo16, hi16], axis=-1).reshape(*w.shape[:-1], 32)
    half = WORDS_PER_LINE // 2
    lo8, hi8 = w_eo[..., :half], w_eo[..., half:]             # (B, 8)

    best = jnp.full(w.shape[:-1], LINE_BYTES, jnp.int32)
    for b, d, payload in _BDI_MODES:
        if b == 8:
            fits = _bdi_fits_b8(lo8, hi8, d)
        elif b == 4:
            fits = _bdi_fits_small(e4, 32, d)
        else:
            fits = _bdi_fits_small(e2, 16, d)
        best = jnp.where(fits & (payload < best), payload, best)

    rep8 = ((lo8 == lo8[..., :1]) & (hi8 == hi8[..., :1])).all(axis=-1)
    zeros = (w == 0).all(axis=-1)
    best = jnp.where(rep8 & ~zeros, 8, best)
    best = jnp.where(zeros, 0, best)
    return best


def _classify_i32(w, slot_idx, key: int):
    """Marker classification; w: (B, 16) int32, slot_idx: (B,) int32."""
    two = 2 * slot_idx + 1
    m2 = two * _M2_I32 + key
    m4 = two * _M4_I32 + key
    j = jax.lax.broadcasted_iota(jnp.int32, w.shape, len(w.shape) - 1)
    il = ((slot_idx[..., None] * WORDS_PER_LINE + j + 1) * _IL_I32 + key)
    tail = _pick(jnp.full(w.shape[:-1], WORDS_PER_LINE - 1, jnp.int32), w)
    is_il = (w == il).all(axis=-1)
    inv = (tail == ~m2) | (tail == ~m4) | (w == ~il).all(axis=-1)
    out = jnp.full(w.shape[:-1], int(LineStatus.UNCOMP), jnp.int32)
    out = jnp.where(inv, int(LineStatus.MAYBE_INVERTED), out)
    out = jnp.where(is_il, int(LineStatus.INVALID), out)
    out = jnp.where(tail == m4, int(LineStatus.COMP4), out)
    out = jnp.where(tail == m2, int(LineStatus.COMP2), out)
    return out


def _scan_kernel(words_ref, words_eo_ref, sizes_ref, fpc_ref, bdi_ref,
                 status_ref, *, key):
    blk = words_ref.shape[0]
    w = words_ref[...]
    base = pl.program_id(0) * blk
    slot = base + jax.lax.broadcasted_iota(jnp.int32, (blk,), 0)
    fpc = _fpc_bytes_i32(w)
    bdi = _bdi_bytes_i32(w, words_eo_ref[...])
    hybrid = jnp.minimum(jnp.minimum(fpc, bdi), LINE_BYTES) + HEADER_BYTES
    for ref, val in ((sizes_ref, hybrid), (fpc_ref, fpc), (bdi_ref, bdi),
                     (status_ref, _classify_i32(w, slot, key))):
        ref[...] = val.reshape(ref.shape)


@functools.partial(jax.jit,
                   static_argnames=("key", "block", "interpret"))
def _scan_call(words, *, key, block, interpret):
    n = words.shape[0]
    grid = n // block
    spec = pl.BlockSpec((block, WORDS_PER_LINE), lambda i: (i, 0))
    # per-line outputs as (blocks, 1, block): a block's minor dims then
    # equal the array's, whatever tile XLA picks for a long 1-D array
    out_spec = pl.BlockSpec((1, 1, block), lambda i: (i, 0, 0))
    out = jax.ShapeDtypeStruct((grid, 1, block), jnp.int32)
    words_eo = jnp.concatenate([words[:, 0::2], words[:, 1::2]], axis=1)
    outs = pl.pallas_call(
        functools.partial(_scan_kernel, key=key),
        grid=(grid,),
        in_specs=[spec, spec],
        out_specs=(out_spec,) * 4,
        out_shape=(out,) * 4,
        interpret=interpret,
    )(words, words_eo)
    return tuple(o.reshape(n) for o in outs)


def compress_scan(lines, *, key: int = DEFAULT_MARKER_KEY, block: int = BLOCK_LINES,
                  interpret: bool | None = None) -> dict:
    """Scan a memory image in one kernel pass.

    lines: (N, 64) uint8 (numpy or jax). Line i is taken to live in slot i.
    Returns a dict of (N,) int32 numpy arrays:
      sizes  — hybrid FPC+BDI compressed size, header included (== the
               bit-true core/compress.compressed_sizes)
      fpc    — FPC-only size in bytes (no header)
      bdi    — best BDI payload size in bytes (no header)
      status — marker classification (core/marker.LineStatus values)
    """
    interpret = resolve_interpret(interpret)
    key = _as_i32(key & 0xFFFFFFFF)
    lines = np.ascontiguousarray(np.asarray(lines, dtype=np.uint8))
    n = lines.shape[0]
    pad = (-n) % block
    if pad:
        lines = np.concatenate(
            [lines, np.zeros((pad, LINE_BYTES), np.uint8)], axis=0)
    words = lines_to_words_i32(lines)
    sizes, fpc, bdi, status = _scan_call(
        words, key=key, block=block, interpret=interpret)
    return {
        "sizes": np.asarray(sizes[:n]),
        "fpc": np.asarray(fpc[:n]),
        "bdi": np.asarray(bdi[:n]),
        "status": np.asarray(status[:n]),
    }
