"""Blockwise attention vs naive reference; decode-vs-forward consistency;
batched fused CRAM decode kernel parity (numerics + bytes output).

Deliberately hypothesis-free: the fused-kernel parity suite here is the
tier-1 gate for `cram_decode_attention_batched` (the hypothesis-sweep
variants live in tests/test_kernels.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.cram_attention import cram_decode_attention
from repro.models.attention import (blockwise_attention,
                                    chunked_decode_attention)


def naive_attention(q, k, v, causal, kv_length=None):
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, kr) / jnp.sqrt(jnp.float32(D))
    T = k.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask[None, None], s, -1e30)
    if kv_length is not None:
        s = jnp.where((jnp.arange(T) < kv_length)[None, None, None],
                      s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), vr)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("qc,kc", [(16, 32), (64, 64)])
def test_blockwise_matches_naive(hq, hkv, causal, qc, kc):
    key = jax.random.key(0)
    B, S, D = 2, 64, 16
    q = jax.random.normal(key, (B, S, hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (B, S, hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, S, hkv, D), jnp.float32)
    out = blockwise_attention(q, k, v, causal=causal, q_chunk=qc,
                              k_chunk=kc)
    ref = naive_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_unrolled_matches_scan():
    key = jax.random.key(3)
    B, S, D = 1, 64, 8
    q = jax.random.normal(key, (B, S, 4, D))
    k = jax.random.normal(jax.random.key(4), (B, S, 2, D))
    v = jax.random.normal(jax.random.key(5), (B, S, 2, D))
    a = blockwise_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    b = blockwise_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16,
                            unroll=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_decode_attention_matches_masked_naive():
    key = jax.random.key(6)
    B, T, Hq, Hkv, D = 2, 128, 8, 2, 16
    q = jax.random.normal(key, (B, Hq, D))
    k = jax.random.normal(jax.random.key(7), (B, T, Hkv, D))
    v = jax.random.normal(jax.random.key(8), (B, T, Hkv, D))
    length = 57
    out = chunked_decode_attention(q, k, v, length=length, k_chunk=32)
    ref = naive_attention(q[:, None], k, v, causal=False,
                          kv_length=length)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("arch", ["phi4_mini_3_8b", "mamba2_130m",
                                  "zamba2_2_7b", "whisper_base"])
def test_decode_matches_teacher_forcing(arch):
    """Stepwise decode logits == full-sequence forward logits."""
    import numpy as np

    from repro.configs import get_smoke
    from repro.models import build
    from repro.models.layers import logits_last

    cfg = get_smoke(arch).replace(remat=False)
    model = build(cfg)
    params, _ = model.init(jax.random.key(0))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model),
                                   cfg.dtype)
        from repro.models import whisper as wh

        enc = wh.encode(params, cfg, frames)
        h = wh.decode_train(params, cfg, toks, enc)
        full_logits = jax.vmap(
            lambda hh: logits_last(hh, params["embed"]), in_axes=1,
            out_axes=1)(h)
        cache = model.init_cache(B, S, enc_len=S)
        cache = wh.whisper_prefill_cross(params, cfg, enc, cache)
        step = jax.jit(model.decode_step)
    else:
        h = model.forward(params, {"tokens": toks})
        full_logits = jax.vmap(
            lambda hh: logits_last(hh, params["embed"]), in_axes=1,
            out_axes=1)(h)
        cache = model.init_cache(B, S)
        step = jax.jit(model.decode_step)
    for i in range(S):
        logits, cache = step(params, toks[:, i:i + 1], cache, jnp.int32(i))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, i]),
            atol=2e-2, rtol=2e-2)


# ----------------- fused CRAM decode kernel: batched parity + bytes

PAGE, HKV, HD = 8, 1, 32
D2 = 2 * HD


def _cram_pages(rng, lanes, n_groups, comp):
    """Logical pages (lanes*n_groups, PAGE, HKV, D2) int16 where group g is
    delta-compressible iff comp[g].  The codec's base is page A's token-0
    row, so compressible groups put EVERY token of every lane within a
    small signed delta of one shared (HKV, D2) row (fits the int4 quad
    range too); incompressible groups are fresh bf16 bit patterns whose
    token rows never fit the delta budget."""
    pages = np.zeros((lanes * n_groups, PAGE, HKV, D2), np.int16)
    for g in range(n_groups):
        base = np.asarray(jnp.asarray(
            rng.normal(size=(HKV, D2)).astype(np.float32),
            jnp.bfloat16).view(jnp.int16))
        for ln in range(lanes):
            if comp[g]:
                delta = rng.integers(-3, 4, size=(PAGE, HKV, D2))
                pages[g * lanes + ln] = base[None] + delta.astype(np.int16)
            else:
                pages[g * lanes + ln] = np.asarray(jnp.asarray(
                    rng.normal(size=(PAGE, HKV, D2)).astype(np.float32),
                    jnp.bfloat16).view(jnp.int16))
    return pages


def _batched_cram_cache(rng, lanes, n_groups, batch, packed="mixed"):
    """Per-sequence caches (stacked leaves, shared markers) with
    per-sequence partial-page valid counts; `packed` says which groups
    delta-compress: a random half ("mixed"), every group ("all") or none
    ("none")."""
    build = (kops.build_cram_cache if lanes == 2
             else kops.build_cram_cache_quad)
    caches, valids = [], []
    n_pages = lanes * n_groups
    for b in range(batch):
        comp = {"mixed": rng.random(n_groups) < 0.5,
                "all": np.ones(n_groups, bool),
                "none": np.zeros(n_groups, bool)}[packed]
        caches.append(build(jnp.asarray(_cram_pages(rng, lanes, n_groups,
                                                    comp)), interpret=True))
        # odd token counts: partial last page + dead tail groups
        tokens = int(rng.integers(1, n_pages * PAGE + 1))
        valids.append(np.clip(tokens - np.arange(n_pages) * PAGE,
                              0, PAGE).astype(np.int32))
    cache = {k: jnp.stack([c[k] for c in caches])
             for k in ("slots", "slots_overflow", "strips", "packed_mask")}
    cache["markers"] = caches[0]["markers"]
    # the layouts asked for must actually be exercised
    ok = np.asarray(cache["packed_mask"])
    assert {"mixed": ok.any() and not ok.all(), "all": ok.all(),
            "none": not ok.any()}[packed], f"want {packed} packed groups"
    return cache, jnp.asarray(np.stack(valids))


def _legacy_vmap_decode(q, cache, vp, lanes):
    """The pre-batched path: the single-sequence kernel vmapped over
    per-sequence physical views (what decode_attention_*_batched did
    before the 2-D grid kernel) — pinned as a parity reference."""
    pv = kops.physical_view if lanes == 2 else kops.physical_view_quad

    def one(qi, slots, over, strips, ok, vpi):
        c = {"slots": slots, "slots_overflow": over, "strips": strips,
             "markers": cache["markers"], "packed_mask": ok}
        s, st, m, v = pv(c, vpi)
        return cram_decode_attention(qi, s, st, m, v, lanes=lanes,
                                     interpret=True)

    return jax.vmap(one)(q, cache["slots"], cache["slots_overflow"],
                         cache["strips"], cache["packed_mask"], vp)


@pytest.mark.parametrize("lanes,batch,packed", [
    pytest.param(2, 3, "mixed", id="2-3"),
    pytest.param(4, 5, "mixed", id="4-5"),
    pytest.param(2, 3, "all", id="2-3-all-packed"),
    pytest.param(4, 5, "all", id="4-5-all-packed"),
    pytest.param(2, 3, "none", id="2-3-none-packed"),
    pytest.param(4, 5, "none", id="4-5-none-packed"),
])
def test_fused_batched_kernel_matches_oracle_and_legacy(lanes, batch,
                                                        packed):
    rng = np.random.default_rng(42 + lanes)
    n_groups = 4
    cache, vp = _batched_cram_cache(rng, lanes, n_groups, batch, packed)
    q = jnp.asarray(rng.normal(size=(batch, 4, HD)).astype(np.float32),
                    jnp.bfloat16)
    ref_fn = (kops.decode_attention_ref_batched if lanes == 2
              else kops.decode_attention_quad_ref_batched)
    ref = np.asarray(ref_fn(q, cache, vp))
    legacy = np.asarray(_legacy_vmap_decode(q, cache, vp, lanes))
    for bg in (1, None, n_groups):
        out, _, _ = kops.decode_attention_fused(q, cache, vp, lanes=lanes,
                                                block_groups=bg,
                                                interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   atol=1e-4, rtol=1e-4)
        # vs the old per-sequence vmap path: same kernel math, the online
        # softmax summed block by block instead of slot by slot — tight
        # tolerance
        np.testing.assert_allclose(np.asarray(out), legacy,
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("lanes", [2, 4])
def test_fused_kernel_bytes_output_bit_exact(lanes):
    """The kernel's second output IS the byte model: per-sequence (raw,
    cram) totals equal `hbm_bytes_moved` exactly, including the
    LLP-mispredict re-probe term under a random predictor."""
    rng = np.random.default_rng(7 + lanes)
    n_groups = 4
    cache, vp = _batched_cram_cache(rng, lanes, n_groups, 3)
    q = jnp.asarray(rng.normal(size=(3, 4, HD)).astype(np.float32),
                    jnp.bfloat16)
    for pred in (None, jnp.asarray(rng.random((3, n_groups)) < 0.5),
                 ~cache["packed_mask"]):   # worst case: every group missed
        bw = kops.hbm_bytes_moved(cache, vp, predictor=pred, lanes=lanes)
        for bg in (1, 2):
            _, raw_s, cram_s = kops.decode_attention_fused(
                q, cache, vp, pred, lanes=lanes, block_groups=bg,
                interpret=True)
            assert np.array_equal(np.asarray(raw_s), bw["raw_per_seq"])
            assert np.array_equal(np.asarray(cram_s), bw["cram_per_seq"])


def test_fused_kernel_shared_cache_path():
    """`decode_attention` (many query rows, ONE shared cache) rides the
    same batched kernel with the batch coordinate pinned in the index
    maps; bytes repeat per row and match the unbatched byte model."""
    rng = np.random.default_rng(3)
    comp = np.array([True, False, True, True])
    cache = kops.build_cram_cache(
        jnp.asarray(_cram_pages(rng, 2, 4, comp)), interpret=True)
    vp = np.clip(50 - np.arange(8) * PAGE, 0, PAGE).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(5, 4, HD)).astype(np.float32),
                    jnp.bfloat16)
    ref = np.asarray(kops.decode_attention_ref(q, cache, vp))
    out, raw_s, cram_s = kops.decode_attention_fused(
        q, cache, jnp.asarray(vp), lanes=2, interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)
    bw = kops.hbm_bytes_moved(cache, vp, lanes=2)
    assert np.asarray(raw_s).tolist() == [bw["raw_bytes"]] * 5
    assert np.asarray(cram_s).tolist() == [bw["cram_bytes"]] * 5


@pytest.mark.parametrize("lanes", [2, 4])
def test_attend_is_block_invariant(lanes):
    """At a fixed block width a sequence attends bit-identically alone in
    a bucket of `bg` groups, beside a longer neighbour in a bucket of
    4·bg groups, and alone with trailing fully masked blocks: the block
    partition does not depend on the bucket and a fully masked block is
    an exact no-op.  A sequence shorter than one block also attends
    alike in a bucket narrower than one block, which is padded to one."""
    rng = np.random.default_rng(11 + lanes)
    bg = 2
    cache, _ = _batched_cram_cache(rng, lanes, 4 * bg, 2)
    q = jnp.asarray(rng.normal(size=(2, 4, HD)).astype(np.float32),
                    jnp.bfloat16)

    def attend(vp, n_seq, n_groups):
        c = {k: x[:n_groups] if k == "markers" else x[:n_seq, :n_groups]
             for k, x in cache.items()}
        out, _, _ = kops.decode_attention_fused(
            q[:n_seq], c, jnp.asarray(vp[:n_seq, :lanes * n_groups]),
            lanes=lanes, block_groups=bg, interpret=True)
        return np.asarray(out)[0]

    for n_live, buckets in ((bg, (bg, 4 * bg)), (1, (1, bg, 4 * bg))):
        vp = np.zeros((2, lanes * 4 * bg), np.int32)
        vp[0, :lanes * n_live] = PAGE
        vp[0, lanes * n_live - 1] = PAGE // 2 + 1      # partial last page
        vp[1] = PAGE                                    # longer neighbour
        alone = attend(vp, 1, buckets[0])
        assert np.isfinite(alone).all()
        for n_groups in buckets[1:]:
            np.testing.assert_array_equal(attend(vp, 1, n_groups), alone)
        np.testing.assert_array_equal(attend(vp, 2, 4 * bg), alone)


@pytest.mark.parametrize("lanes", [2, 4])
def test_dead_overflow_slots_are_never_read(lanes):
    """Every group packed and every overflow slot holding random bits
    (NaN and Inf patterns among them): the kernel answers as the oracle
    does over zeroed overflow slots, so a packed group's dead overflow
    slots are never read as tokens."""
    rng = np.random.default_rng(5 + lanes)
    cache, vp = _batched_cram_cache(rng, lanes, 4, 2, "all")
    q = jnp.asarray(rng.normal(size=(2, 4, HD)).astype(np.float32),
                    jnp.bfloat16)
    ref_fn = (kops.decode_attention_ref_batched if lanes == 2
              else kops.decode_attention_quad_ref_batched)
    ref = np.asarray(ref_fn(q, cache, vp))
    over = cache["slots_overflow"]
    garbage = dict(cache, slots_overflow=jnp.asarray(
        rng.integers(-2**15, 2**15, size=over.shape), jnp.int16))
    for bg in (1, 2):
        out, _, _ = kops.decode_attention_fused(
            q, garbage, vp, lanes=lanes, block_groups=bg, interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   atol=1e-4, rtol=1e-4)
