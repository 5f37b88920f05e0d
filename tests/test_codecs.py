"""FPC / BDI / hybrid codec properties: exact round-trips + size laws +
numpy/jax.numpy backend parity (property-based; see test_codec_registry.py
for the deterministic cross-backend suite)."""

import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.compression import bdi, fpc
from repro.compression import hybrid as compress

LINE = 64


def lines_strategy():
    # mix of structured and random lines: the structured ones exercise
    # every FPC pattern and BDI mode
    return st.sampled_from([
        "zeros", "small_words", "rep_bytes", "rep8", "base_delta8",
        "base_delta4", "halfwords", "random",
    ]).flatmap(lambda kind: st.integers(0, 2**32 - 1).map(
        lambda seed: _make_line(kind, seed)))


def _make_line(kind: str, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "zeros":
        out = np.zeros(LINE, np.uint8)
        if seed % 3 == 0:  # sprinkle a couple of nonzeros
            out[rng.integers(0, LINE, 2)] = rng.integers(1, 255, 2)
        return out
    if kind == "small_words":
        w = rng.integers(-8, 8, 16).astype("<i4")
        return w.view(np.uint8).copy()
    if kind == "rep_bytes":
        w = np.repeat(rng.integers(0, 256, 16).astype(np.uint8), 4)
        return w[:LINE].copy()
    if kind == "rep8":
        return np.tile(rng.integers(0, 256, 8).astype(np.uint8), 8)
    if kind == "base_delta8":
        base = rng.integers(-2**62, 2**62, dtype=np.int64)
        d = rng.integers(-100, 100, 8).astype(np.int64)
        return (base + d).astype("<i8").view(np.uint8).copy()
    if kind == "base_delta4":
        base = rng.integers(-2**30, 2**30, dtype=np.int64)
        d = rng.integers(-100, 100, 16)
        return (base + d).astype("<i4").view(np.uint8).copy()
    if kind == "halfwords":
        h = rng.integers(-128, 128, 32).astype("<i2")
        return h.view(np.uint8).copy()
    return rng.integers(0, 256, LINE).astype(np.uint8)


@given(lines_strategy())
def test_fpc_roundtrip_and_size(line):
    packed = fpc.fpc_pack(line)
    out = fpc.fpc_unpack(packed)
    assert np.array_equal(out, line)
    assert len(packed) == int(fpc.fpc_size_bytes(line.reshape(1, LINE))[0])
    assert 1 <= len(packed) <= LINE + 6  # worst case: 3-bit prefix overhead


@given(lines_strategy())
def test_bdi_roundtrip(line):
    arr = line.reshape(1, LINE)
    sizes, modes = bdi.bdi_sizes(arr)
    mode = int(modes[0])
    payload = bdi.bdi_pack_batch(arr, mode)
    assert payload.shape[1] == bdi.PAYLOAD_BYTES[mode] == int(sizes[0])
    out = bdi.bdi_unpack_batch(payload, mode)
    assert np.array_equal(out, arr)


@given(lines_strategy())
def test_hybrid_roundtrip(line):
    blob = compress.compress_line(line)
    out, consumed = compress.decompress_line(blob)
    assert consumed == len(blob)
    assert np.array_equal(out, line)
    assert len(blob) == int(
        compress.compressed_sizes(line.reshape(1, LINE))[0])
    assert len(blob) <= LINE + 1 + 6


def test_bdi_modes_exact_sizes():
    # zeros -> 0B payload; rep8 -> 8B; B8D1 -> 17B
    zeros = np.zeros((1, LINE), np.uint8)
    s, m = bdi.bdi_sizes(zeros)
    assert int(m[0]) == bdi.M_ZEROS and int(s[0]) == 0
    rep = np.tile(np.arange(8, dtype=np.uint8), 8).reshape(1, LINE)
    s, m = bdi.bdi_sizes(rep)
    assert int(m[0]) == bdi.M_REP8 and int(s[0]) == 8
    b8 = (np.int64(10**15) + np.arange(8)).astype("<i8").view(
        np.uint8).reshape(1, LINE)
    s, m = bdi.bdi_sizes(b8)
    assert int(m[0]) == bdi.M_B8D1 and int(s[0]) == 17


def test_vectorized_batch_consistency():
    batch = np.stack([_make_line(k, i) for i, k in enumerate(
        ["zeros", "rep8", "base_delta4", "random"] * 8)])
    sizes = compress.compressed_sizes(batch)
    for i, line in enumerate(batch):
        assert int(sizes[i]) == len(compress.compress_line(line))


def test_jnp_size_path_matches_numpy():
    import jax.numpy as jnp
    from jax import enable_x64

    batch = np.stack([_make_line("base_delta4", i) for i in range(16)]
                     + [_make_line("random", i) for i in range(16)])
    np_sizes = fpc.fpc_size_bytes(batch)
    with enable_x64():
        j_sizes = np.asarray(fpc.fpc_size_bytes(jnp.asarray(batch), xp=jnp))
        nb, jb = bdi.bdi_sizes(batch), bdi.bdi_sizes(jnp.asarray(batch),
                                                     xp=jnp)
    assert np.array_equal(np_sizes, j_sizes)
    assert np.array_equal(np.asarray(nb[0]), np.asarray(jb[0]))


# ----------------------------------------------------- xp-parity (property)
# Adversarial word menu: each 32-bit word is drawn to sit ON a pattern/size
# boundary (sign flips, exact range edges, zero-run splice points), the
# places where a vectorized size law and a bit-exact packer most easily
# disagree.

_WORD_MENU = (
    0, 1, 7, 8, -8 & 0xFFFFFFFF, -9 & 0xFFFFFFFF,           # se4 edges
    127, 128, -128 & 0xFFFFFFFF, -129 & 0xFFFFFFFF,         # se8 edges
    32767, 32768, -32768 & 0xFFFFFFFF, -32769 & 0xFFFFFFFF,  # se16 edges
    0x00010000, 0xFFFF0000, 0x7FFF0000,                     # pad16
    0x00800080, 0x7F807F80, 0x0080FF80,                     # half_se8 edges
    0xABABABAB, 0x01010101,                                 # repeated bytes
    0xDEADBEEF, 0x80000000, 0x7FFFFFFF,                     # raw
)


def adversarial_lines():
    """Lines assembled word-by-word from boundary values + random words."""
    word = st.one_of(st.sampled_from(_WORD_MENU),
                     st.integers(0, 2**32 - 1))
    return st.lists(word, min_size=16, max_size=16).map(
        lambda ws: np.asarray(ws, dtype="<u4").view(np.uint8).copy())


@given(adversarial_lines())
def test_xp_parity_sizes_vs_exact_pack(line):
    """fpc_size_bits / compressed_sizes agree between the numpy and
    jax.numpy backends AND with the exact bit-level packers."""
    import jax.numpy as jnp
    from jax import enable_x64

    arr = line.reshape(1, LINE)
    words = arr.view("<u4").reshape(1, 16)
    np_bits = int(fpc.fpc_size_bits(words)[0])
    np_hybrid = int(compress.compressed_sizes(arr)[0])
    with enable_x64():
        j_bits = int(np.asarray(
            fpc.fpc_size_bits(jnp.asarray(words), xp=jnp))[0])
        j_hybrid = int(np.asarray(
            compress.compressed_sizes(jnp.asarray(arr), xp=jnp))[0])
    assert np_bits == j_bits
    assert np_hybrid == j_hybrid
    # the exact packers pin the vectorized size laws
    assert len(fpc.fpc_pack(line)) == (np_bits + 7) // 8
    assert len(compress.compress_line(line)) == np_hybrid
    assert np.array_equal(fpc.fpc_unpack(fpc.fpc_pack(line)), line)


@given(adversarial_lines())
def test_xp_parity_bdi_sizes(line):
    import jax.numpy as jnp
    from jax import enable_x64

    arr = line.reshape(1, LINE)
    ns, nm = bdi.bdi_sizes(arr)
    with enable_x64():
        js, jm = bdi.bdi_sizes(jnp.asarray(arr), xp=jnp)
    assert int(ns[0]) == int(js[0]) and int(nm[0]) == int(jm[0])
    payload = bdi.bdi_pack_batch(arr, int(nm[0]))
    assert payload.shape[1] == int(ns[0])


def test_group_packing():
    from repro.compression.marker import MarkerSpec

    spec = MarkerSpec()
    lines = [np.zeros(LINE, np.uint8),
             np.tile(np.arange(8, dtype=np.uint8), 8)]
    slot = compress.pack_group(lines, spec.marker2(0))
    assert slot is not None and slot.shape == (LINE,)
    out = compress.unpack_group(slot, 2)
    assert np.array_equal(out[0], lines[0])
    assert np.array_equal(out[1], lines[1])
    # incompressible pair must not fit
    rng = np.random.default_rng(0)
    bad = [rng.integers(0, 256, LINE).astype(np.uint8) for _ in range(2)]
    assert compress.pack_group(bad, spec.marker2(0)) is None
