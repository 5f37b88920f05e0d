"""Codec/layout registry: cross-backend round-trips (ISSUE 4 suite).

Each registered codec must agree across its three truths: the bit-true
numpy pack/unpack, the vectorized xp-generic size function (numpy AND
jax.numpy), and the Pallas device backend (interpret mode) — plus the
layout registry invariants the engine and KV cache build on.
"""

import numpy as np
import pytest

from repro.compression import codecs, framing, layouts, pagepack
from repro.compression.framing import LINE_BYTES

# ----------------------------------------------------------- deterministic
# structured line corpus: exercises every FPC pattern and BDI mode without
# needing hypothesis (the property-based variants live in test_codecs.py)


def _corpus():
    rng = np.random.default_rng(0xC0DEC)
    lines = [np.zeros(LINE_BYTES, np.uint8)]
    lines.append(np.tile(np.arange(8, dtype=np.uint8), 8))          # rep8
    lines.append(np.repeat(rng.integers(0, 256, 16), 4)
                 .astype(np.uint8)[:LINE_BYTES])                    # rep bytes
    lines.append(rng.integers(-8, 8, 16).astype("<i4")
                 .view(np.uint8))                                   # se4
    lines.append((np.int64(10**15) + np.arange(8)).astype("<i8")
                 .view(np.uint8))                                   # b8d1
    lines.append((np.int64(2**29) + rng.integers(-100, 100, 16))
                 .astype("<i4").view(np.uint8))                     # b4d1
    lines.append(rng.integers(-128, 128, 32).astype("<i2")
                 .view(np.uint8))                                   # halfwords
    for _ in range(8):
        lines.append(rng.integers(0, 256, LINE_BYTES).astype(np.uint8))
    # zero-run boundaries
    z = np.zeros(LINE_BYTES, np.uint8)
    z[4:8] = 0xAB
    lines.append(z)
    return np.stack([np.ascontiguousarray(l) for l in lines])


@pytest.mark.parametrize("name", ["raw", "bdi", "fpc", "hybrid"])
def test_line_codec_roundtrip_and_size(name):
    codec = codecs.get_codec(name)
    assert codec.unit == "line64"
    lines = _corpus()
    sizes = np.asarray(codec.sizes(lines))
    for i, line in enumerate(lines):
        blob = codec.pack_line(line)
        out, consumed = codec.unpack_line(blob, 0)
        assert np.array_equal(out, line), f"{name} line {i}"
        assert consumed == len(blob) == int(sizes[i]), f"{name} line {i}"


@pytest.mark.parametrize("name", ["raw", "bdi", "fpc", "hybrid"])
def test_line_codec_xp_size_parity(name):
    import jax.numpy as jnp
    from jax import enable_x64

    codec = codecs.get_codec(name)
    lines = _corpus()
    np_sizes = np.asarray(codec.sizes(lines))
    with enable_x64():
        j_sizes = np.asarray(codec.sizes(jnp.asarray(lines), xp=jnp))
    assert np.array_equal(np_sizes, j_sizes)


def test_compress_scan_is_a_registered_backend():
    """The Pallas scan's size columns equal the registry size functions."""
    from repro.kernels.compress_scan import compress_scan

    lines = _corpus()
    out = compress_scan(lines, interpret=True)
    hybrid = np.asarray(codecs.get_codec("hybrid").sizes(lines))
    fpc = np.asarray(codecs.get_codec("fpc").sizes(lines))
    bdi = np.asarray(codecs.get_codec("bdi").sizes(lines))
    assert np.array_equal(out["sizes"], hybrid)
    assert np.array_equal(out["fpc"], fpc)
    # the scan's bdi column is the raw payload; the registry adds the
    # 1-byte self-describing mode header
    assert np.array_equal(out["bdi"] + 1, bdi)


# ------------------------------------------------------------- page codecs

def _kv_pages(rng, n, compressible=True, *, page=8, hkv=2, d2=16):
    row = rng.integers(-1000, 1000, (1, hkv, d2)).astype(np.int16)
    base = np.broadcast_to(row, (page, hkv, d2))
    out = []
    for _ in range(n):
        if compressible:
            p = base + rng.integers(-8, 8, base.shape)
        else:
            p = rng.integers(-(2**14), 2**14, base.shape)
        out.append(p.astype(np.int16))
    out[0][0] = base[0]          # lane A's token-0 row IS the base
    return out


@pytest.mark.parametrize("name,n", [("int8-delta", 2), ("int4-delta", 4)])
@pytest.mark.parametrize("compressible", [True, False])
def test_page_codec_three_backends_agree(name, n, compressible):
    """numpy pagepack == jnp ref == Pallas kernel (interpret), bit-for-bit."""
    import jax.numpy as jnp

    codec = codecs.get_codec(name)
    assert codec.unit == "page" and codec.group_lanes == n
    rng = np.random.default_rng(7 + n)
    pages = _kv_pages(rng, n, compressible)
    # numpy bit-true reference
    ok_np, packed_np, base_np = codec.pack_pages(*pages, xp=np)
    assert bool(ok_np) == compressible
    if ok_np:
        rt = codec.unpack_pages(packed_np, base_np, xp=np)
        for got, want in zip(rt, pages, strict=True):
            assert np.array_equal(got, want)
    # jnp path
    ok_j, packed_j, base_j = codec.pack_pages(
        *[jnp.asarray(p) for p in pages], xp=jnp)
    assert bool(ok_j) == bool(ok_np)
    assert np.array_equal(np.asarray(packed_j), packed_np)
    # Pallas backend (pack returns (packed, base, ok))
    pack_k, unpack_k = codec.pallas()
    packed_k, base_k, ok_k = pack_k(
        *[jnp.asarray(p) for p in pages], interpret=True)
    assert bool(ok_k) == bool(ok_np)
    assert np.array_equal(np.asarray(packed_k), packed_np)
    assert np.array_equal(np.asarray(base_k), base_np)
    out_k = unpack_k(jnp.asarray(packed_np), jnp.asarray(base_np),
                     interpret=True)
    want = codec.unpack_pages(packed_np, base_np, xp=np)
    for got, ref in zip(out_k, want, strict=True):
        assert np.array_equal(np.asarray(got), ref)


def test_marker_domains_never_alias():
    pair = framing.slot_markers(256, domain=framing.DOMAIN_PAIR)
    quad = framing.slot_markers(256, domain=framing.DOMAIN_QUAD)
    assert not (pair == quad).any()
    # pair domain is bit-identical to the historical marker family
    from repro.kernels import ref

    assert np.array_equal(pair, ref.slot_markers(256))


# ---------------------------------------------------------------- registry

def test_registry_surface():
    assert set(codecs.codec_names("line64")) == {
        "raw", "bdi", "fpc", "hybrid"}
    assert set(codecs.codec_names("page")) == {"int8-delta", "int4-delta"}
    assert set(layouts.layout_names()) == {"group4", "kv-pair", "kv-quad"}
    with pytest.raises(KeyError):
        codecs.get_codec("lz77")
    with pytest.raises(KeyError):
        layouts.get_layout("group8")


def test_schemes_name_registry_entries():
    from repro.core import schemes

    assert schemes.get("cram").codec == "hybrid"
    assert schemes.get("cram").layout == "group4"
    assert schemes.get("baseline").codec == "raw"
    with pytest.raises(KeyError):
        schemes.Scheme("bogus", codec="nope")
    with pytest.raises(KeyError):
        schemes.Scheme("bogus", layout="nope")


def test_layout_probe_chain_and_predictor_table():
    from repro.compression.predictor import probe_count_table

    g4 = layouts.get_layout("group4")
    assert g4.probe_chain(3, 2) == [2, 3, 0]
    t = probe_count_table(g4)
    assert t.shape == (5, 4, 3)
    # lane 0 always takes exactly one probe
    assert (t[:, 0, :] == 1).all()
    kvp = layouts.get_layout("kv-pair")
    tp = probe_count_table(kvp)
    # packed state, lane 1, predicted packed (level 1 -> slot 0... via
    # pred_slot[1][1] = 0): hit on first probe
    assert tp[1, 1, 1] == 1
    # packed state, lane 1, predicted uncompressed: probes slot 1 (IL),
    # then slot 0 -> 2 probes
    assert tp[1, 1, 0] == 2


def test_checkpoint_codec_uses_registry():
    from repro.checkpoint.codec import (
        cram_compress_bytes,
        cram_decompress_bytes,
    )

    raw = (np.arange(4096, dtype=np.int32) // 7).tobytes() + b"tail"
    for name in ("bdi", "hybrid", "fpc", "raw"):
        blob = cram_compress_bytes(raw, codec=name)
        assert cram_decompress_bytes(blob) == raw, name
    with pytest.raises(ValueError):
        cram_compress_bytes(raw, codec="int8-delta")
