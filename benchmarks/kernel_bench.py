"""CRAM-KV kernel micro-bench: pack/unpack/fused-attention timings + the
bandwidth savings on compressible vs incompressible KV streams, plus the
checkpoint codec's compression ratio per tensor class (the Fig. 4 story on
our own data).  Kernels run as `kernels.resolve_interpret` decides:
compiled on a TPU, interpreted on the CPU, where timings are structural
only and no device metric."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.codec import cram_compress_bytes
from repro.kernels import ops
from repro.kv import CRAMKVCache


def _timeit(fn, *args, n=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def blockspec_sweep(*, batch=4, n_groups=8, page=8, hkv=1, d=32,
                    n_timing=5, seed=0) -> dict:
    """BlockSpec tuning for the batched fused decode kernel: time every
    block_groups tiling of the slot axis per lanes mode, with parity
    columns (numerics vs the jnp oracle, bytes bit-exact vs the analytic
    `hbm_bytes_moved` model) so a tiling that breaks semantics can never
    look fast.  CI runs this (`--sweep kernels`) and fails on any parity
    row; the committed snapshot is BENCH_kernels.json.

    On the CPU the kernels are interpreted and the timings are
    structural (relative cost of the tilings and the fused-vs-reference
    gap); on a TPU they time the compiled kernels.  `backend` names which."""
    rng = np.random.default_rng(seed)
    d2 = 2 * d

    def mk_group(lanes, compressible):
        base = 2.0 + rng.standard_normal((1, 1, hkv, d2)) * 0.25
        if compressible:
            x = base * (1 + rng.standard_normal(
                (lanes, page, hkv, d2)) * 1e-4)
        else:
            x = rng.standard_normal((lanes, page, hkv, d2))
        return np.asarray(jnp.asarray(x.astype(jnp.bfloat16))
                          .view(jnp.int16))

    report: dict = {"batch": batch, "n_groups": n_groups, "page": page,
                    "n_kv": hkv, "head_dim": d, "modes": {},
                    "backend": {"platform": jax.devices()[0].platform,
                                "device_kind": jax.devices()[0].device_kind}}
    for lanes in (2, 4):
        build = (ops.build_cram_cache if lanes == 2
                 else ops.build_cram_cache_quad)
        caches, valids = [], []
        for _ in range(batch):
            pages = np.concatenate([
                mk_group(lanes, bool(rng.random() < 0.7))
                for _ in range(n_groups)])
            caches.append(build(jnp.asarray(pages)))
            tokens = int(rng.integers(1, lanes * n_groups * page + 1))
            valids.append(np.clip(
                tokens - np.arange(lanes * n_groups) * page,
                0, page).astype(np.int32))
        cache = {k: jnp.stack([c[k] for c in caches])
                 for k in ("slots", "slots_overflow", "strips",
                           "packed_mask")}
        cache["markers"] = caches[0]["markers"]
        vp = jnp.asarray(np.stack(valids))
        q = jnp.asarray(rng.standard_normal((batch, 4, d)), jnp.bfloat16)
        ref_fn = (ops.decode_attention_ref_batched if lanes == 2
                  else ops.decode_attention_quad_ref_batched)
        ref = np.asarray(ref_fn(q, cache, vp))
        bw = ops.hbm_bytes_moved(cache, vp, lanes=lanes)
        rows, best = [], None
        for bg in (1, 2, 4, n_groups, None):
            out, raw_s, cram_s = ops.decode_attention_fused(
                q, cache, vp, lanes=lanes, block_groups=bg)
            err = float(np.max(np.abs(np.asarray(out, np.float32) - ref)))
            bytes_ok = (np.array_equal(np.asarray(raw_s),
                                       bw["raw_per_seq"])
                        and np.array_equal(np.asarray(cram_s),
                                           bw["cram_per_seq"]))
            us = _timeit(lambda qq, lanes=lanes, bg=bg:
                         ops.decode_attention_fused(
                             qq, cache, vp, lanes=lanes,
                             block_groups=bg)[0], q, n=n_timing)
            row = {"block_groups": bg, "us_per_call": round(us, 1),
                   "max_err_vs_oracle": err,
                   "numerics_parity": err < 2e-2,
                   "bytes_bit_exact": bool(bytes_ok)}
            rows.append(row)
            if row["numerics_parity"] and row["bytes_bit_exact"] and (
                    best is None or us < best["us_per_call"]):
                best = row
        report["modes"][f"lanes{lanes}"] = {
            "rows": rows,
            "best_block_groups": best["block_groups"] if best else None,
            "saving_on_mix": round(bw["saving"], 4),
        }
    report["parity_ok"] = all(
        r["numerics_parity"] and r["bytes_bit_exact"]
        for m in report["modes"].values() for r in m["rows"])
    return report


def run() -> list[tuple]:
    rng = np.random.default_rng(0)
    rows = []
    page, hkv, d = 32, 2, 64
    d2 = 2 * d

    def mk_pages(n, compressible):
        base = (2.0 + rng.standard_normal((1, 1, hkv, d2)) * 0.25)
        if compressible:
            x = base * (1 + rng.standard_normal((n, page, hkv, d2)) * 2e-3)
        else:
            x = rng.standard_normal((n, page, hkv, d2))
        return jnp.asarray(x.astype(jnp.bfloat16)).view(jnp.int16)

    for label, comp in (("compressible", True), ("incompressible", False)):
        pages = mk_pages(8, comp)
        t_pack = _timeit(lambda p: ops.build_cram_cache(p)["slots"], pages)
        cache = ops.build_cram_cache(pages)
        valid = jnp.full((8,), page, jnp.int32)
        q = jnp.asarray(rng.standard_normal((2, 4, d)), jnp.float32)
        t_att = _timeit(lambda qq: ops.decode_attention(qq, cache, valid), q)
        err = float(jnp.max(jnp.abs(
            ops.decode_attention(q, cache, valid)
            - ops.decode_attention_ref(q, cache, valid))))
        bw = ops.hbm_bytes_moved(cache, valid)
        rows.append((f"kernel/pack_{label}", t_pack,
                     f"packed={int(np.asarray(cache['packed_mask']).sum())}/4"))
        rows.append((f"kernel/attend_{label}", t_att,
                     f"bw_saving={bw['saving']:.3f} err={err:.1e}"))

    # incremental CRAM-KV decode: per-step pack work is O(new pairs)
    from repro.kv import synthetic_kv_stream

    kvc = CRAMKVCache(max_pages=12, page=page, n_kv=hkv, head_dim=d,
                      policy="static")
    stream, _ = synthetic_kv_stream(np.random.default_rng(1), 1, 12 * page,
                                    hkv, d)
    kvc.append(stream[:, : 6 * page], stream[:, : 6 * page])
    kvc.account_step()
    kvc.append(stream[:, 6 * page:6 * page + 1],
               stream[:, 6 * page:6 * page + 1])
    kvc.account_step()          # warm-up: compile W=1 window before timing
    pairs0 = kvc.stats.pack_pairs_processed
    t0 = time.perf_counter()
    n_steps = 8
    for t in range(6 * page + 1, 6 * page + 1 + n_steps):
        kvc.append(stream[:, t:t + 1], stream[:, t:t + 1])
        kvc.account_step()
    t_step = (time.perf_counter() - t0) / n_steps * 1e6
    pack_per_step = (kvc.stats.pack_pairs_processed - pairs0) / n_steps
    rows.append(("kernel/kv_decode_step", t_step,
                 f"pack_pairs/step={pack_per_step:.1f} "
                 f"saving={kvc.saving():.3f}"))

    # checkpoint codec ratios per tensor class
    classes = {
        "zeros": np.zeros(1 << 16, np.uint8).tobytes(),
        "adam_moments": (lambda m: m.tobytes())(
            np.where(rng.random(1 << 14) < 0.7, 0,
                     rng.standard_normal(1 << 14) * 1e-9).astype("<f4")),
        "weights_fp32": (rng.standard_normal(1 << 14) * 0.02
                         ).astype("<f4").tobytes(),
        "token_ids": rng.integers(0, 32000, 1 << 14).astype(
            "<i4").tobytes(),
    }
    for name, raw in classes.items():
        t0 = time.perf_counter()
        blob = cram_compress_bytes(raw)
        dt = (time.perf_counter() - t0) * 1e6
        rows.append((f"ckpt_codec/{name}", dt,
                     f"ratio={len(raw)/len(blob):.2f}x"))
    return rows
