#!/usr/bin/env python3
"""Run the CRAM-KV serve path once on a TPU and check what comes out.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded paths, on four chips

One chip, in one process (nothing is spawned):

  a. device   — jax.devices()[0] must be a TPU; otherwise exit 2 with no
                result line.  There is no CPU fallback.
  b. launcher — `repro.launch.serve.main` serves phi4-mini-3.8b at its
                published width and depth with bf16 parameters (batch 4,
                prompt 128, 32 generated tokens, --kv-policy auto, 2 serve
                slots so the spill tier churns).  Tokens must be in the
                vocabulary, logits finite, every sequence admitted and
                retired, at least one evicted, and the spill ledger must
                count exactly the crossings the scheduler made.
  c. CRAM-KV  — a ServeLoop at phi4-mini's KV geometry (page 16, 8 KV
                heads, head_dim 128), fed the model's own layer-0 KV
                (incompressible) and a compressible synthetic stream, in
                pair and quad packing: bulk prefill, decode steps, then
                `ServeLoop.attend`.  Resident slots must equal
                `slot_reference_state` bit for bit, attend must match
                `attend_ref` within ATTEND_TOL, the compressible runs must
                pack, and the megastep and decode programs must hold a
                Mosaic kernel (`tpu_custom_call`), not interpret mode.
  d. set-up   — compile and wall times and the launcher's tokens/s are
                printed as set-up information (host clock, compiles
                included where said); they are not benchmark metrics.

`--chips 4` runs only the two sharded paths a user reaches through
shard="auto" — the slot-axis decode-attend (`serving/shard.py`) and the
workload-axis sweep (`core/batchsim.py`) — each against its single-device
dispatch, bit for bit.

Every phase raises on failure; the last stdout line is the JSON result
{"ok": true, "device": {...}} only when all of them passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "phi4_mini_3_8b"
BATCH, PROMPT, GEN, SERVE_SLOTS = 4, 128, 32, 2
PAGE, DECODE_STEPS = 16, 4
ATTEND_TOL = 1e-3          # f32 kernel vs f32 oracle, both HIGHEST matmuls


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def cache_counter() -> dict:
    """Persistent-compile-cache hits and misses seen by this process."""
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def phase_launcher() -> dict:
    """(b) The launcher at full width.  Returns its report."""
    from repro import configs
    from repro.launch import serve

    t0 = time.time()
    out = serve.main(["--arch", ARCH, "--batch", str(BATCH),
                      "--prompt-len", str(PROMPT), "--gen", str(GEN),
                      "--kv-policy", "auto", "--slots", str(SERVE_SLOTS)])
    wall = time.time() - t0
    cfg = configs.get(configs.canonical(ARCH))
    m = out["model"]
    assert (m["d_model"], m["n_layers"]) == (cfg.d_model, cfg.n_layers), m
    assert m["param_dtype"] == "bfloat16", m
    toks = np.asarray(out["tokens"])
    assert toks.shape == (BATCH, GEN), toks.shape
    assert ((toks >= 0) & (toks < m["vocab"])).all(), "token out of vocab"
    assert out["logits_finite"], "non-finite logits"
    tier = out["serve_tier"]
    assert tier["admitted"] == tier["retired"] == BATCH, tier
    assert tier["evicted"] >= 1, tier
    sp = tier["spill_tier"]
    assert sp["spills"] == tier["evicted"] + tier["spilled_direct"], tier
    assert sp["restores"] == tier["woken"], tier
    rows = [ev for tc in out["traffic"].get("kv", {}).values()
            for name, ev in tc.items() if name == "spill"]
    assert sum(r["count"] for r in rows) == sp["spills"] + sp["restores"]
    log(f"phase b ok: {cfg.name} d_model {m['d_model']} x {m['n_layers']} "
        f"layers, {m['param_dtype']} params; admitted {tier['admitted']} "
        f"retired {tier['retired']} evicted {tier['evicted']} woken "
        f"{tier['woken']} spilled_direct {tier['spilled_direct']}")
    log(f"setup: launcher wall {wall:.1f} s (model init and compiles "
        f"included); its host-clock prefill {out['prefill_tokens_per_s']} "
        f"tok/s, decode {out['tokens_per_s']} tok/s (set-up info, not "
        f"metrics)")
    return out


def _spec(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


def _assert_compiled(cache, mega_text: str, decode_text: str) -> None:
    """The serve path ran Mosaic kernels, not interpret mode."""
    assert cache.interpret is False, "kernels ran interpreted"
    assert "tpu_custom_call" in mega_text, "megastep holds no TPU kernel"
    assert "tpu_custom_call" in decode_text, "decode holds no TPU kernel"


def _run_loop(k, v, packing, seed):
    """Prefill BATCH sequences, decode DECODE_STEPS tokens, attend.

    k/v: (BATCH, >= PROMPT + DECODE_STEPS, n_kv, hd).  Returns (loop,
    attend outputs by slot, q by slot, compiled megastep text)."""
    from repro.serving import ServeLoop
    from repro.serving import slots as slots_mod

    n_kv, hd = k.shape[2], k.shape[3]
    total = PROMPT + DECODE_STEPS
    loop = ServeLoop(slots=BATCH, max_pages=-(-total // PAGE), page=PAGE,
                     n_kv=n_kv, head_dim=hd, policy="static",
                     packing=packing)
    for sid in range(BATCH):
        loop.prefill(sid, k[sid, :PROMPT], v[sid, :PROMPT])
    orig, seen = slots_mod._megastep, {}

    def spy(*a, **kw):
        seen.setdefault("call", (jax.tree.map(_spec, a), kw))
        return orig(*a, **kw)

    slots_mod._megastep = spy
    try:
        for t in range(PROMPT, total):
            loop.step_all({sid: (k[sid, t:t + 1], v[sid, t:t + 1])
                           for sid in range(BATCH)})
    finally:
        slots_mod._megastep = orig
    rng = np.random.default_rng(seed)
    n_q = 3 * n_kv                       # phi4-mini's 24 query heads
    q = rng.standard_normal((BATCH, n_q, hd)).astype(np.float32)
    out = loop.attend({sid: q[loop.seqs[sid].slot] for sid in range(BATCH)})
    a, kw = seen["call"]
    mega_text = orig.lower(*a, **kw).compile().as_text()
    return loop, {loop.seqs[s].slot: o for s, o in out.items()}, q, mega_text


def _decode_text(cache) -> str:
    """The compiled text of the decode program `attend` just ran."""
    from repro.kernels import ops as kops

    n = cache._active_bucket()
    kc = {k: _spec(x) for k, x in cache._kernel_cache(n).items()}
    valid = jnp.asarray(cache.valid_per_page()[:, : cache.group_lanes * n])
    q = jax.ShapeDtypeStruct((cache.batch, 3 * cache.n_kv, cache.d),
                             jnp.float32)
    return kops.decode_attention_fused.lower(
        q, kc, valid, lanes=cache.group_lanes,
        interpret=cache.interpret).compile().as_text()


def phase_cram_kv(layer_kv) -> None:
    """(c) ServeLoop on the chip against its oracles."""
    from repro.kv.traffic import synthetic_kv_stream

    km, vm = layer_kv
    n_kv, hd = km.shape[2], km.shape[3]
    rng = np.random.default_rng(7)
    ks, vs = synthetic_kv_stream(rng, BATCH, PROMPT + DECODE_STEPS, n_kv,
                                 hd, compressible=True)
    for source, k, v in (("model", km, vm), ("compressible", ks, vs)):
        for packing in ("pair", "quad"):
            t0 = time.time()
            loop, out, q, mega_text = _run_loop(k, v, packing, seed=11)
            cache = loop.cache
            for slot in out:
                ref = cache.slot_reference_state(slot)
                phys = cache.slot_physical_state(slot)
                for key in ref:
                    assert np.array_equal(np.asarray(ref[key]),
                                          np.asarray(phys[key])), (
                        source, packing, slot, key)
            with jax.default_matmul_precision("highest"):
                ref_out = np.asarray(cache.attend_ref(q))
            err = max(float(np.max(np.abs(np.asarray(o) - ref_out[slot])))
                      for slot, o in out.items())
            for slot, o in out.items():
                np.testing.assert_allclose(np.asarray(o), ref_out[slot],
                                           atol=ATTEND_TOL, rtol=ATTEND_TOL)
            packed = int(np.asarray(cache.state["packed_mask"]).sum())
            groups = int(np.asarray(cache.state["packed_mask"]).size)
            if source == "compressible":
                assert packed > 0, f"{packing}: nothing packed"
            _assert_compiled(cache, mega_text, _decode_text(cache))
            log(f"phase c ok: {source} KV, {packing}: slots == "
                f"slot_reference_state bitwise, attend max |err| {err:.3e} "
                f"(tol {ATTEND_TOL}), {packed}/{groups} groups packed, "
                f"megastep+decode lowered to tpu_custom_call "
                f"({time.time() - t0:.1f} s set-up wall incl. compiles)")


def phase_sharded() -> None:
    """--chips 4: the two shard="auto" paths vs one device, bit for bit."""
    from repro.core.batchsim import sweep
    from repro.core.engine import SimConfig
    from repro.kv.traffic import synthetic_kv_stream
    from repro.serving import ServeLoop
    from repro.serving.shard import shard_kv_attend

    n_dev = len(jax.devices())
    rng = np.random.default_rng(3)
    n_kv, hd = 8, 128
    for packing in ("pair", "quad"):
        loop = ServeLoop(slots=n_dev * 2, max_pages=8, page=PAGE, n_kv=n_kv,
                         head_dim=hd, policy="static", packing=packing)
        for sid in range(loop.n_slots):
            t = int(rng.integers(1, 8 * PAGE))
            k, v = synthetic_kv_stream(rng, 1, t, n_kv, hd,
                                       compressible=bool(sid % 2))
            loop.prefill(sid, k[0], v[0])
        q = rng.standard_normal((loop.n_slots, 3 * n_kv, hd)).astype(
            np.float32)
        sharded = np.asarray(shard_kv_attend(loop.cache, q, shard=True))
        single = np.asarray(shard_kv_attend(loop.cache, q, shard=False))
        assert np.array_equal(sharded, single), packing
        log(f"chips {n_dev}: slot-axis shard_map attend == single device "
            f"bitwise ({packing}, {loop.n_slots} slots)")
    cfg = SimConfig(llc_sets=16, llc_ways=2, n_groups=512)
    w, t = n_dev, 2000
    addrs = rng.integers(0, cfg.n_groups * 4, (w, t)).astype(np.int32)
    wr = rng.random((w, t)) < 0.3
    pab = rng.random((w, cfg.n_groups)) < 0.6
    pcd = rng.random((w, cfg.n_groups)) < 0.6
    quad = rng.random((w, cfg.n_groups)) < 0.3
    schemes = ("baseline", "cram", "dynamic")
    sharded = sweep(schemes, addrs, wr, pab, pcd, quad, cfg, shard=True)
    single = sweep(schemes, addrs, wr, pab, pcd, quad, cfg, shard=False)
    assert np.array_equal(sharded, single)
    log(f"chips {n_dev}: workload-axis shard_map sweep == single device "
        f"bitwise ({w} workloads x {len(schemes)} schemes)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths on four chips")
    args = ap.parse_args(argv)

    dev = device_info()                                   # (a)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev}); nothing run",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees {dev}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counts = cache_counter()
    log(f"phase a ok: {dev}; compile cache {cache_dir}")
    t0 = time.time()
    if args.chips == 4:
        phase_sharded()
    else:
        phase_cram_kv(phase_launcher()["layer_kv"])
    log(f"setup: all phases {time.time() - t0:.1f} s wall; persistent "
        f"compile cache {counts['hits']} hits, {counts['misses']} misses")
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
