"""Pallas TPU kernels: BDI-style page packing of KV pages (CRAM-KV).

The device backends of the registered page codecs
(repro.compression.codecs):

  * int8-delta (pack_pair/unpack_pair) — packs a pair of (page, Hkv, D2)
    int16 pages into a single slot of int8 delta-pairs against a shared
    base strip (pageA's token-0 row), reporting whether the pair fits;
  * int4-delta (pack_quad/unpack_quad) — packs FOUR pages into one slot of
    int4 delta-nibbles against the same base (4:1).

Layout/semantics match the xp-generic bit-true reference in
repro.compression.pagepack (and its jnp wrappers in kernels/ref.py)
exactly — allclose-tested in interpret mode by the cross-backend
round-trip tests.

BlockSpec notes (TPU target): D2 = 2*head_dim = 256 lanes (2x the 128-lane
register width); one program holds one page group, each page a
(page, Hkv, D2) VMEM tile (16 x 8 x 256 x 2B = 64KB at page 16) — one
slot is one DMA.  Leading axes of the inputs form the grid, so a whole
window of groups packs in one launch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret


def _pack_kernel(a_ref, b_ref, packed_ref, base_ref, ok_ref):
    a = a_ref[0].astype(jnp.int32)           # (page, Hkv, D2)
    b = b_ref[0].astype(jnp.int32)
    base = a[0]                              # (Hkv, D2)
    da = a - base[None]
    db = b - base[None]
    ok = jnp.all((da >= -128) & (da <= 127)
                 & (db >= -128) & (db <= 127))
    packed = ((db & 0xFF) << 8) | (da & 0xFF)
    packed_ref[0] = jax.lax.bitcast_convert_type(
        packed.astype(jnp.uint16), jnp.int16)
    base_ref[0] = base.astype(jnp.int16)
    ok_ref[...] = jnp.full(ok_ref.shape, ok, jnp.int32)


def _unpack_kernel(packed_ref, base_ref, a_ref, b_ref):
    v = jax.lax.bitcast_convert_type(
        packed_ref[0], jnp.uint16).astype(jnp.int32)
    base = base_ref[0].astype(jnp.int32)
    lo = ((v & 0xFF) ^ 0x80) - 0x80          # sign-extend low byte
    hi = (((v >> 8) & 0xFF) ^ 0x80) - 0x80
    a_ref[0] = (base[None] + lo).astype(jnp.int16)
    b_ref[0] = (base[None] + hi).astype(jnp.int16)


def _pack_call(kernel, name, pages, interpret):
    """Grid the pack kernel over every leading (batch) index of `pages`.

    pages: tuple of (..., page, Hkv, D2) int16 arrays of one shape.  The
    leading axes flatten into one grid axis, one page group per program,
    so callers pass whole windows instead of vmapping the pallas_call —
    a vmapped `(1,)` fit flag tiles illegally on the TPU.  The fit flag
    rides as an (N, 1, 1) array: its block's minor dims equal the
    array's, the one rank that satisfies Mosaic's tiling rule here."""
    lead = pages[0].shape[:-3]
    page, hkv, d2 = pages[0].shape[-3:]
    n = math.prod(lead)
    flat = [p.reshape(n, page, hkv, d2) for p in pages]
    blk = pl.BlockSpec((1, page, hkv, d2), lambda i: (i, 0, 0, 0))
    packed, base, ok = pl.pallas_call(
        kernel,
        name=name,
        grid=(n,),
        in_specs=[blk] * len(flat),
        out_specs=[blk, pl.BlockSpec((1, hkv, d2), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))],
        out_shape=(
            jax.ShapeDtypeStruct((n, page, hkv, d2), jnp.int16),
            jax.ShapeDtypeStruct((n, hkv, d2), jnp.int16),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.int32),
        ),
        interpret=resolve_interpret(interpret),
    )(*flat)
    return (packed.reshape(pages[0].shape), base.reshape(lead + (hkv, d2)),
            ok.reshape(lead) > 0)


def _unpack_call(kernel, name, packed, base, n_pages, interpret):
    """Grid an unpack kernel over the leading axes of `packed`."""
    lead = packed.shape[:-3]
    page, hkv, d2 = packed.shape[-3:]
    n = math.prod(lead)
    blk = pl.BlockSpec((1, page, hkv, d2), lambda i: (i, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(n,),
        in_specs=[blk, pl.BlockSpec((1, hkv, d2), lambda i: (i, 0, 0))],
        out_specs=[blk] * n_pages,
        out_shape=tuple(jax.ShapeDtypeStruct((n, page, hkv, d2), jnp.int16)
                        for _ in range(n_pages)),
        interpret=resolve_interpret(interpret),
    )(packed.reshape(n, page, hkv, d2), base.reshape(n, hkv, d2))
    return tuple(o.reshape(packed.shape) for o in out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_pair(page_a, page_b, *, interpret: bool | None = None):
    """(..., page, Hkv, D2) int16 x2 -> (packed int16 (..., page, Hkv, D2),
    base int16 (..., Hkv, D2), ok bool (...)); any leading axes batch."""
    return _pack_call(_pack_kernel, "pack_pair", (page_a, page_b), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_pair(packed, base, *, interpret: bool | None = None):
    return _unpack_call(_unpack_kernel, "unpack_pair", packed, base, 2,
                        interpret)


def _pack_quad_kernel(a_ref, b_ref, c_ref, d_ref, packed_ref, base_ref,
                      ok_ref):
    a = a_ref[0].astype(jnp.int32)           # (page, Hkv, D2)
    base = a[0]                              # (Hkv, D2)
    da = a - base[None]
    db = b_ref[0].astype(jnp.int32) - base[None]
    dc = c_ref[0].astype(jnp.int32) - base[None]
    dd = d_ref[0].astype(jnp.int32) - base[None]
    fits = lambda x: (x >= -8) & (x <= 7)
    ok = jnp.all(fits(da) & fits(db) & fits(dc) & fits(dd))
    packed = ((dd & 0xF) << 12) | ((dc & 0xF) << 8) | ((db & 0xF) << 4) \
        | (da & 0xF)
    packed_ref[0] = jax.lax.bitcast_convert_type(
        packed.astype(jnp.uint16), jnp.int16)
    base_ref[0] = base.astype(jnp.int16)
    ok_ref[...] = jnp.full(ok_ref.shape, ok, jnp.int32)


def _unpack_quad_kernel(packed_ref, base_ref, a_ref, b_ref, c_ref, d_ref):
    v = jax.lax.bitcast_convert_type(
        packed_ref[0], jnp.uint16).astype(jnp.int32)
    base = base_ref[0].astype(jnp.int32)
    se4 = lambda x: (x ^ 0x8) - 0x8          # sign-extend int4
    a_ref[0] = (base[None] + se4(v & 0xF)).astype(jnp.int16)
    b_ref[0] = (base[None] + se4((v >> 4) & 0xF)).astype(jnp.int16)
    c_ref[0] = (base[None] + se4((v >> 8) & 0xF)).astype(jnp.int16)
    d_ref[0] = (base[None] + se4((v >> 12) & 0xF)).astype(jnp.int16)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_quad(page_a, page_b, page_c, page_d, *,
              interpret: bool | None = None):
    """Four (..., page, Hkv, D2) int16 pages -> (packed, base, ok), batched
    over any leading axes like `pack_pair`."""
    return _pack_call(_pack_quad_kernel, "pack_quad",
                      (page_a, page_b, page_c, page_d), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_quad(packed, base, *, interpret: bool | None = None):
    return _unpack_call(_unpack_quad_kernel, "unpack_quad", packed, base, 4,
                        interpret)
