"""Pallas TPU kernels: fused CRAM decode attention.

Flash-decode over a CRAM-packed paged KV cache: the grid walks physical
slots; each program DMAs a *block* of slots + their base strips into
VMEM, checks the strip-tail markers (implicit metadata — no separate
status fetch), inlines the delta unpack for packed slots (one DMA yields
TWO pages for the int8-delta pair codec or FOUR for the int4-delta quad
codec: the paper's bandwidth win), and accumulates online-softmax
partials in VMEM scratch.  The final step normalizes into the output.

Two kernels:

  * `cram_decode_attention` — the original single-sequence kernel,
    `grid=(n_slots,)`, one slot per program.  Kept only as the
    interpret-mode parity reference for the batched kernel (tests pin
    new-vs-old parity); its rank-1 marker block does not tile on a TPU.
  * `cram_decode_attention_batched` — the serve-path kernel: a 2-D grid
    `(batch, slot_block)` where each program DMAs `block_groups *
    lanes` slots of one sequence under tunable BlockSpecs (swept by
    `benchmarks/kernel_bench.py`, snapshot in BENCH_kernels.json).  It
    emits a SECOND output: per-sequence (raw, cram) bytes-moved for
    exactly the layout the kernel walked — packed slot+strip vs raw
    slots, including the LLP-mispredict re-probe term — so the serve
    loop's bandwidth accounting is a kernel by-product instead of a
    separate pass over the same state (`kernels/ops.hbm_bytes_moved`
    stays as the standalone/oracle reduction; the kernel output matches
    it bit-exactly, pinned by tests/test_attention_numerics.py).

The batched kernel compiles for the TPU (tests/test_tpu_compile.py at
head_dim 128, page 16).  Each program builds its block's token tile by
page group, not by slot: a packed group gives the `lanes` pages
delta-decoded from its lead slot and strip, a raw group lane 0 of each
of its slots, chosen by a jnp.where on the lead slot's marker verdict (a
pl.when branch is ROADMAP S4), so a packed group's dead overflow slots
are never taken as tokens.  It then attends to the whole tile once per
KV head: one QK^T matmul for the head's query group (GQA), one masked
online-softmax update, one PV matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret
from .ref import MARKER_LANES

NEG_INF = -1e30

# The serve-path kernel's name in the compiled program: a profiler trace
# shows its op as `cram_decode_attention_batched.<n>` whatever the Python
# function is called.
KERNEL_NAME = "cram_decode_attention_batched"

# Default slot-block width (page groups per program) for the batched
# kernel.  On a TPU v5e at page 16, 8 KV heads, head_dim 128 and 1024
# groups the kernel takes 10.0 / 9.2 / 9.2 ms (pair) and 17.7 / 17.7 /
# 16.8 ms (quad) for a batch of 8 at 4 / 8 / 16 groups a block; a wider
# block compiles longer.  PERF.md keeps the curve.
DEFAULT_BLOCK_GROUPS = 8


def _kernel(q_ref, slot_ref, strip_ref, marker_ref, valid_ref,
            out_ref, m_s, l_s, acc_s, *, lanes):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG_INF)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])

    q = q_ref[...].astype(jnp.float32)              # (Hq, D)
    slot = slot_ref[0]                              # (page, Hkv, D2) int16
    strip = strip_ref[0]                            # (Hkv, D2+2) int16
    page, hkv, d2 = slot.shape
    d = d2 // 2
    hq = q.shape[0]
    g = hq // hkv

    # --- implicit metadata: compare the strip-tail marker lanes
    tail = strip[:, -MARKER_LANES:].astype(jnp.int32)
    tail_u = (tail[:, 0] & 0xFFFF) | ((tail[:, 1] & 0xFFFF) << 16)
    expected = marker_ref[0]
    is_packed = jnp.all(tail_u == expected)

    # --- decode both interpretations, select by marker
    base = strip[:, :d2].astype(jnp.int32)          # (Hkv, D2)
    v_u = jax.lax.bitcast_convert_type(slot, jnp.uint16).astype(jnp.int32)
    if lanes == 2:                                  # int8-delta pair codec
        lo = ((v_u & 0xFF) ^ 0x80) - 0x80
        hi = (((v_u >> 8) & 0xFF) ^ 0x80) - 0x80
        packed_pages = [base[None] + lo, base[None] + hi]
    else:                                           # int4-delta quad codec
        se4 = lambda x: (x ^ 0x8) - 0x8
        packed_pages = [base[None] + se4((v_u >> s) & 0xF)
                        for s in (0, 4, 8, 12)]
    zeros = jnp.zeros_like(slot)
    pages = [jnp.where(is_packed, p.astype(jnp.int16),
                       slot if j == 0 else zeros)
             for j, p in enumerate(packed_pages)]

    kv = jnp.stack(pages)                           # (lanes, page, Hkv, D2)
    kvf = jax.lax.bitcast_convert_type(kv, jnp.bfloat16).astype(jnp.float32)
    k = kvf[..., :d].reshape(lanes * page, hkv, d)
    v = kvf[..., d:].reshape(lanes * page, hkv, d)

    valid = valid_ref[0]                            # (lanes,) int32 per page
    tok = jax.lax.broadcasted_iota(jnp.int32, (lanes, page), 1)
    mask = (tok < valid[:, None]).reshape(lanes * page)

    kg = jnp.repeat(k, g, axis=1)                   # (T, Hq, D)
    vg = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("hd,thd->ht", q, kg,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / (d ** 0.5))
    s = jnp.where(mask[None, :], s, NEG_INF)

    m_prev = m_s[:, 0]
    l_prev = l_s[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_s[...] = acc_s[...] * alpha[:, None] + jnp.einsum(
        "ht,thd->hd", p, vg, preferred_element_type=jnp.float32)
    m_s[...] = m_new[:, None]
    l_s[...] = l_new[:, None]

    @pl.when(i == n - 1)
    def _finalize():
        out_ref[...] = acc_s[...] / jnp.maximum(l_s[...][:, 0:1], 1e-30)


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def cram_decode_attention(q, slots, strips, markers, valid, *,
                          lanes: int = 2, interpret: bool | None = None):
    """q (Hq, D); slots (n,page,Hkv,D2) i16; strips (n,Hkv,D2+2) i16;
    markers (n,) int32 (expected pack markers); valid (n,lanes) int32 valid
    tokens per logical page.  `lanes` selects the slot format: 2 = pair
    (int8-delta), 4 = quad (int4-delta).  Returns (Hq, D) float32."""
    n, page, hkv, d2 = slots.shape
    hq, d = q.shape
    assert lanes in (2, 4)
    return pl.pallas_call(
        functools.partial(_kernel, lanes=lanes),
        name="cram_decode_attention",
        grid=(n,),
        in_specs=[
            pl.BlockSpec((hq, d), lambda i: (0, 0)),
            pl.BlockSpec((1, page, hkv, d2), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, hkv, d2 + MARKER_LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
            pl.BlockSpec((1, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((hq, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, slots, strips, markers, valid)


# --------------------------------------------------------- batched kernel


def _batched_kernel(q_ref, slot_ref, strip_ref, marker_ref, valid_ref,
                    pred_ref, out_ref, bytes_ref, m_s, l_s, acc_s, byt_s,
                    *, lanes, slot_bytes, strip_bytes):
    """One (sequence, slot block) program.  Shapes as the kernel sees them:

    q (1, Hkv, G, D) f32 — query heads grouped by their KV head; slots
    (1, K, page, Hkv, D2) i16; strips (1, K, Hkv, D2+2) i16; markers
    (1, K, 1, 2) i16 expected strip-tail lanes; valid (1, K, 1, lanes) i32;
    pred (1, 1, 1, K // lanes) i32.  Every small side input carries unit
    dims so that its block's two minor dims equal the array's — the TPU
    tiling rule — and every per-slot / per-page quantity is a (1, 1)
    vector, so the body needs no lane<->sublane relayout."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG_INF)
        byt_s[...] = jnp.zeros_like(byt_s[...])

    _, kk, page, hkv, d2 = slot_ref.shape
    d = d2 // 2
    scale = 1.0 / (d ** 0.5)
    hi = jax.lax.Precision.HIGHEST

    def marker_ok(tail, expected):
        """(.., Hkv, 2) strip tails vs (.., 1, 2) marker lanes -> packed?
        Every head's tail must carry the marker."""
        eq = (tail.astype(jnp.int32) == expected.astype(jnp.int32))
        eq = eq.astype(jnp.int32)
        return jnp.min(jnp.min(eq, axis=-1, keepdims=True), axis=-2,
                       keepdims=True) > 0

    is_packed = marker_ok(strip_ref[0, :, :, d2:], marker_ref[0])  # (K,1,1)

    # The block's token tile, by page group: a packed group contributes
    # the `lanes` pages delta-decoded from its lead slot and strip, a raw
    # group lane 0 of each of its `lanes` slots.  Group packedness is the
    # lead slot's marker verdict, so a packed group's overflow slots are
    # never taken as tokens; each page's valid count comes from the slot
    # its tokens come from.  Row r of the tile is token r % page of page
    # r // page.
    bg = kk // lanes
    tb = kk * page                        # tokens the block's tile holds
    tok = jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    mask = jnp.zeros((1, tb), jnp.bool_)
    for gi in range(bg):
        lead = gi * lanes
        for ln in range(lanes):
            n_valid = jnp.where(is_packed[lead],
                                valid_ref[0, lead, :, ln:ln + 1],
                                valid_ref[0, lead + ln, :, 0:1])  # (1, 1)
            off = (lead + ln) * page
            mask = mask | ((tok >= off) & (tok < off + n_valid))

    # One attend per KV head over the whole tile: one QK^T matmul, one
    # masked online-softmax update, one PV matmul.  The block partition
    # depends only on the block width, and a fully masked block is an
    # exact no-op (alpha 1, p 0), so a sequence attends bit-identically
    # alone or among longer neighbours.  A bf16 bit pattern b widens
    # exactly to the f32 bit pattern b << 16, so raw and delta-decoded
    # values go straight to f32 (the int16 wrap of base + delta falls off
    # the top of the shift).  A sequence's first block sets `l` and `acc`
    # where later blocks rescale and add, so no zero start can be folded
    # into the PV matmul: the CPU interpreter would otherwise fuse the
    # matmul of a one- or two-program grid differently and change its
    # last bits.
    grp_packed = is_packed.reshape(bg, lanes, 1, 1)[:, 0:1]   # (bg,1,1,1)
    for h in range(hkv):
        x = slot_ref[0, :, :, h, :].astype(jnp.int32).reshape(
            bg, lanes, page, d2)
        lead_u = x[:, 0] & 0xFFFF                              # (bg,page,D2)
        base = strip_ref[0, :, pl.ds(h, 1), :d2].astype(jnp.int32).reshape(
            bg, lanes, 1, d2)[:, 0]                            # (bg, 1, D2)
        if lanes == 2:                              # int8-delta pair codec
            deltas = [((lead_u & 0xFF) ^ 0x80) - 0x80,
                      (((lead_u >> 8) & 0xFF) ^ 0x80) - 0x80]
        else:                                       # int4-delta quad codec
            deltas = [(((lead_u >> sh) & 0xF) ^ 0x8) - 0x8
                      for sh in (0, 4, 8, 12)]
        packed = jnp.stack([base + dl for dl in deltas], axis=1)
        kvf = jax.lax.bitcast_convert_type(
            (jnp.where(grp_packed, packed, x) << 16).reshape(tb, d2),
            jnp.float32)                                       # (tb, D2)
        q = q_ref[0, h].astype(jnp.float32)                   # (G, D)
        s = jax.lax.dot_general(
            q, kvf[:, :d], (((1,), (1,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)                       # (G, tb)
        m_prev = m_s[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p_sum = jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p, kvf[:, d:], precision=hi,
                     preferred_element_type=jnp.float32)
        l_s[h] = jnp.where(j == 0, p_sum, l_s[h] * alpha + p_sum)
        acc_s[h] = jnp.where(j == 0, pv, acc_s[h] * alpha + pv)
        m_s[h] = m_new

    # --- bytes-moved for exactly the layout this block walked.  Flat-slot
    # form of the `ops.hbm_bytes_moved` group model: the lead slot of a
    # packed group carries all `lanes` valid counts (overflow slots dead),
    # a raw group spreads one live page per slot — so per-slot sums equal
    # the per-group sums bit-for-bit.  The LLP-miss re-probe is charged
    # once per mispredicted LIVE group: group packedness is the lead
    # slot's marker verdict, group liveness the union over its slots.
    raw_b = jnp.zeros((1, 1), jnp.int32)
    cram_b = jnp.zeros((1, 1), jnp.int32)
    for gi in range(bg):
        grp_live = jnp.zeros((1, 1), jnp.bool_)
        for i in range(gi * lanes, (gi + 1) * lanes):
            n_live = sum((valid_ref[0, i, :, ln:ln + 1] > 0).astype(jnp.int32)
                         for ln in range(lanes))              # (1, 1)
            grp_live = grp_live | (n_live > 0)
            raw_b = raw_b + n_live * slot_bytes
            cram_b = cram_b + jnp.where(
                is_packed[i] & (n_live > 0), slot_bytes + strip_bytes,
                n_live * (slot_bytes + strip_bytes))
        pred = pred_ref[0, 0, :, gi:gi + 1] != 0
        cram_b = cram_b + jnp.where(
            (pred != is_packed[gi * lanes]) & grp_live, slot_bytes, 0)
    byt_s[:, 0:1] += raw_b
    byt_s[:, 1:2] += cram_b

    @pl.when(j == nj - 1)
    def _finalize():
        out_ref[0] = acc_s[...] / jnp.maximum(l_s[...], 1e-30)
        bytes_ref[0] = byt_s[...]


def resolve_block_groups(n_groups: int, block_groups: int | None) -> int:
    """Largest divisor of `n_groups` not exceeding the requested width."""
    bg = DEFAULT_BLOCK_GROUPS if block_groups is None else block_groups
    bg = max(1, min(bg, n_groups))
    while n_groups % bg:
        bg -= 1
    return bg


@functools.partial(jax.jit, static_argnames=("lanes", "block_groups",
                                             "shared_cache", "interpret"))
def cram_decode_attention_batched(q, slots, strips, markers, valid,
                                  predictor, *, lanes: int = 2,
                                  block_groups: int | None = None,
                                  shared_cache: bool = False,
                                  interpret: bool | None = None):
    """Batched fused decode: one 2-D grid `(batch, slot_block)` program.

    q (B, Hq, D); slots (B, n, page, Hkv, D2) int16 — or (n, page, Hkv,
    D2) with `shared_cache=True` (every query row walks the same slot
    list); strips (B?, n, Hkv, D2+2); markers (n,) int32 shared across
    the batch; valid (B?, n, lanes) int32 valid tokens per logical page;
    predictor (B?, n // lanes) predicted group packedness (the LLP
    analog; pass the actual packed mask for a perfect predictor).

    Each program DMAs `block_groups * lanes` consecutive slots + strips
    of one sequence (`block_groups` is the tunable BlockSpec axis, swept
    by benchmarks/kernel_bench.py); fewer groups than one block are
    padded to one with groups that hold no valid token.  Returns (out
    (B, Hq, D) float32, bytes (B, 2) int32) where bytes[b] = (raw, cram)
    bytes one decode step DMAs for sequence b under the layout the kernel
    walked — bit-identical to `ops.hbm_bytes_moved` per-sequence totals.
    """
    assert lanes in (2, 4)
    b, hq, d = q.shape
    if shared_cache:
        slots, strips = slots[None], strips[None]
        valid, predictor = valid[None], predictor[None]
    bc, n, page, hkv, d2 = slots.shape
    n_groups = n // lanes
    wanted = DEFAULT_BLOCK_GROUPS if block_groups is None else block_groups
    if n_groups < wanted:
        # pad a bucket narrower than one block to one block, with groups
        # that hold no valid token: its tokens then meet the same block,
        # and the same matmul shapes, as in a wider bucket's first block
        pad = (wanted - n_groups) * lanes
        widen = lambda x, ax, by=pad: jnp.pad(
            x, [(0, by if i == ax else 0) for i in range(jnp.ndim(x))])
        slots, strips, valid = widen(slots, 1), widen(strips, 1), widen(
            valid, 1)
        markers = widen(markers, 0)
        predictor = widen(predictor, 1, wanted - n_groups)
        n, n_groups = n + pad, wanted
    bg = resolve_block_groups(n_groups, block_groups)
    kk = bg * lanes
    nj = n // kk
    g = hq // hkv
    slot_bytes = page * hkv * d2 * 2
    strip_bytes = hkv * (d2 + MARKER_LANES) * 2
    # side inputs in their kernel shapes (see `_batched_kernel`): query
    # heads grouped by KV head, markers as the strip-tail lanes they must
    # equal (low 16 bits, high 16 bits), unit dims for the tiling rule
    qg = q.reshape(b, hkv, g, d)
    m = jnp.asarray(markers, jnp.int32)
    mk = jax.lax.bitcast_convert_type(
        jnp.stack([m & 0xFFFF, (m >> 16) & 0xFFFF], -1).astype(jnp.uint16),
        jnp.int16).reshape(1, n, 1, MARKER_LANES)
    vv = jnp.asarray(valid, jnp.int32).reshape(bc, n, 1, lanes)
    pred = jnp.asarray(predictor).astype(jnp.int32).reshape(bc, nj, 1, bg)
    # shared caches keep one copy in HBM: the index map pins the batch
    # coordinate to 0 instead of materializing B replicas
    bix = (lambda bi: 0) if shared_cache else (lambda bi: bi)
    out, bts = pl.pallas_call(
        functools.partial(_batched_kernel, lanes=lanes,
                          slot_bytes=slot_bytes, strip_bytes=strip_bytes),
        name=KERNEL_NAME,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda bi, j: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kk, page, hkv, d2),
                         lambda bi, j: (bix(bi), j, 0, 0, 0)),
            pl.BlockSpec((1, kk, hkv, d2 + MARKER_LANES),
                         lambda bi, j: (bix(bi), j, 0, 0)),
            pl.BlockSpec((1, kk, 1, MARKER_LANES),
                         lambda bi, j: (0, j, 0, 0)),
            pl.BlockSpec((1, kk, 1, lanes),
                         lambda bi, j: (bix(bi), j, 0, 0)),
            pl.BlockSpec((1, 1, 1, bg), lambda bi, j: (bix(bi), j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda bi, j: (bi, 0, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda bi, j: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, 2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((1, 2), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(qg, slots, strips, mk, vv, pred)
    return out.reshape(b, hq, d), bts.reshape(b, 2)
