"""Serving launcher: model decode + continuous-batching CRAM-KV tier.

Runs a model end-to-end (prefill via teacher-forced forward, then step
decoding with the dense cache) and mirrors one layer's REAL KV
stream through the production serve tier (`repro.serving.ServeLoop`):
a fixed pool of `--slots` batch lanes with slot reuse, staggered admits
every `--admit-rate` steps, and a compressed host spill tier behind them
(`--spill-pages` caps it).  With `--slots` smaller than the batch, cold
sequences spill COMPRESSED and wake on their next decode step — every
crossing books a ledger `spill` event with compressed duals, so the
printed traffic is the serve tier's whole byte story.

This module is deliberately thin: scheduling, spill, sharded attend and
per-tier autotuning all live in `repro.serving`; the launcher only maps
CLI flags onto one ServeLoop and feeds it the model's KV traffic.

`--arch` serves the published configuration at full width and depth;
`--smoke` shrinks it (d_model 128, 2 layers) and `--preset` picks a tiny
CPU model.  Serving stores parameters in bfloat16.

  python -m repro.launch.serve --arch phi4_mini_3_8b \
      --batch 4 --prompt-len 128 --slots 2 --kv-policy auto
  python -m repro.launch.serve --arch phi4_mini_3_8b --smoke   # on a CPU
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..bandwidth import AutoTuner, Ledger
from ..models import build, smoke_config
from ..runtime.compile_cache import enable_compile_cache
from ..serving import ServeLoop
from .steps import make_serve_step
from .train import PRESETS


def _layer_kv(cache):
    """The first attention layer's decode KV, (B, T, hkv, hd) numpy k and
    v — or None for a stack without one."""
    spec_key = next((k for k in sorted(cache) if k.startswith("b")
                     and "attn" in cache[k]), None)
    if spec_key is None:
        return None
    return (np.asarray(cache[spec_key]["attn"]["k"])[0],
            np.asarray(cache[spec_key]["attn"]["v"])[0])


def _serve_tier(args, cfg, kcache, vcache, ledger, *, prompt_len,
                total_tokens):
    """Continuous-batching mirror of one layer's KV stream: staggered
    admits into `--slots` lanes, per-step decode appends, retire at end
    of stream — spill crossings happen whenever live > slots."""
    hkv, hd = cfg.n_kv_heads, cfg.hd
    page = 16
    P, T = prompt_len, total_tokens
    B = kcache.shape[0]
    n_need = -(-T // page)
    kw = dict(slots=args.slots or B, max_pages=max(n_need, 2), page=page,
              n_kv=hkv, head_dim=hd, spill_pages=args.spill_pages,
              ledger=ledger, fused=not args.unfused,
              migrate_budget=args.migrate_budget,
              async_spill=not args.sync_spill)
    choices = None
    if args.kv_policy == "auto":
        # auto picks BOTH tiers' packings; --spill-packing only applies
        # to the explicit-policy path
        loop, ch = ServeLoop.auto(AutoTuner(), kcache[:, :P],
                                  vcache[:, :P], **kw)
        choices = {tier: c.as_dict() for tier, c in ch.items()}
    else:
        loop = ServeLoop(policy=args.kv_policy, packing=args.kv_packing,
                         spill_packing=args.spill_packing, **kw)

    admit_every = max(args.admit_rate, 1)
    admit_at = {i: i * admit_every for i in range(B)}
    fed: dict[int, int] = {}                  # seq -> tokens consumed
    step_no = 0
    while len(fed) < B or any(t < T for t in fed.values()):
        for i in range(B):
            if admit_at[i] == step_no:
                # whole prompt in ONE bulk-pack dispatch (or straight to
                # the spill tier when the pool is full and this admit is
                # the coldest) — not a token-by-token replay
                loop.prefill(i, kcache[i, :P], vcache[i, :P])
                fed[i] = P
        kvs = {i: (kcache[i, fed[i]:fed[i] + 1],
                   vcache[i, fed[i]:fed[i] + 1])
               for i in loop.seqs if fed[i] < T}
        if kvs:
            loop.step_all(kvs)      # wakes spilled seqs named this step;
            # with live > slots the appends run in waves of `slots`
            for i in kvs:
                fed[i] += 1
                if fed[i] >= T:
                    loop.retire(i)
        step_no += 1
    obs = loop.observe_tiers()
    stats = {
        **loop.summary(),
        "serve_steps": step_no,
        "policy": args.kv_policy,
        "policy_choice": choices,
        "tier_observations": obs or None,   # per-tier §VI counters
    }
    return stats, ledger


def _timed_decode(serve_step, params, prompts, cache, *, gen):
    """Prefill and step decode as two SEPARATELY timed regions, each with
    ZERO device->host materialization inside (analysis R3): the prefill
    region syncs the cache before its clock stops, per-step decode tokens
    stay device arrays, the last step is synced before the decode timer
    stops, and the host copies happen after both.  Also returns the last
    decode step's logits (B, vocab).
    tests/test_launch_timing.py pins the ordering."""
    P = prompts.shape[1]
    t0 = time.time()
    for i in range(P - 1):
        _, cache, _ = serve_step(params, jnp.asarray(prompts[:, i:i + 1]),
                                 cache, jnp.int32(i))
    jax.block_until_ready(cache)
    prefill_wall = time.time() - t0
    generated = []
    tok = jnp.asarray(prompts[:, -1:])
    t1 = time.time()
    for i in range(P - 1, P + gen - 1):
        tok, cache, logits = serve_step(params, tok, cache, jnp.int32(i))
        generated.append(tok)            # device array — no per-step sync
    jax.block_until_ready((generated, cache, logits))
    decode_wall = time.time() - t1
    gen_arr = np.stack([np.asarray(t)[:, 0] for t in generated], 1)
    return gen_arr, cache, np.asarray(logits), prefill_wall, decode_wall


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4_mini_3_8b")
    ap.add_argument("--preset", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="shrink --arch to CPU size (smoke_config)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--slots", type=int, default=0,
                    help="serve-tier batch lanes (0 = one per sequence; "
                         "fewer than --batch exercises the spill tier)")
    ap.add_argument("--spill-pages", type=int, default=None,
                    help="host spill-tier capacity in pages (default "
                         "unbounded)")
    ap.add_argument("--admit-rate", type=int, default=1,
                    help="admit one new sequence every N serve steps "
                         "(staggered continuous batching)")
    ap.add_argument("--kv-policy", default="dynamic",
                    choices=["dynamic", "static", "off", "auto"])
    ap.add_argument("--kv-packing", default="pair",
                    choices=["pair", "quad"],
                    help="hot-tier packing (ignored with --kv-policy "
                         "auto, where the AutoTuner picks per tier)")
    ap.add_argument("--spill-packing", default="quad",
                    choices=["off", "pair", "quad"],
                    help="spill-tier packing (auto overrides it)")
    ap.add_argument("--migrate-budget", type=int, default=1,
                    help="page-group columns re-laid per decode step when "
                         "a live gate flip / packing switch is migrating "
                         "the cache (0 disables incremental migration)")
    ap.add_argument("--unfused", action="store_true",
                    help="run the legacy append/repack/account dispatch "
                         "sequence instead of the fused megastep")
    ap.add_argument("--sync-spill", action="store_true",
                    help="re-encode spill payloads inline on evict "
                         "instead of on the background worker")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.preset:
        cfg = PRESETS[args.preset]
    else:
        cfg = configs.get(configs.canonical(args.arch))
        cfg = smoke_config(cfg) if args.smoke else cfg
    cfg = cfg.replace(param_dtype=jnp.bfloat16)
    model = build(cfg)
    # jitted so the f32 draws fuse into the bf16 parameters: eager init
    # would hold an f32 copy of the largest stacked weight beside them
    params = jax.jit(lambda key: model.init(key)[0])(
        jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    B, P, G = args.batch, args.prompt_len, args.gen
    # decode attention walks the cache in whole k-chunks
    chunk = cfg.attn_k_chunk
    max_len = P + G if P + G <= chunk else -(-(P + G) // chunk) * chunk
    prompts = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)

    serve_step = jax.jit(make_serve_step(model))

    # warm-up on a throwaway cache so tokens_per_s excludes jit compile time
    jax.block_until_ready(serve_step(
        params, jnp.asarray(prompts[:, :1]),
        model.init_cache(B, max_len), jnp.int32(0)))
    cache = model.init_cache(B, max_len)

    # model prefill: teacher-forced token by token (correct for every
    # family); the serve TIER below ingests each prompt in one bulk pack
    gen, cache, logits, prefill_wall, decode_wall = _timed_decode(
        serve_step, params, prompts, cache, gen=G)

    ledger = Ledger("serve")
    kv_stats = None
    layer_kv = (_layer_kv(cache)
                if cfg.family in ("dense", "moe", "vlm", "hybrid") else None)
    if layer_kv is not None:
        kv_stats, ledger = _serve_tier(args, cfg, *layer_kv, ledger,
                                       prompt_len=P,
                                       total_tokens=P + G - 1)

    out = {
        "name": cfg.name, "batch": B, "prompt_len": P, "generated": G,
        "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                  "vocab": cfg.vocab,
                  "param_dtype": jnp.dtype(cfg.param_dtype).name},
        "prefill_tokens_per_s": round(B * (P - 1)
                                      / max(prefill_wall, 1e-9), 1),
        "tokens_per_s": round(B * G / max(decode_wall, 1e-9), 1),
        "sample": gen[0][:16].tolist(),
        "tokens": gen.tolist(),
        "logits_finite": bool(np.isfinite(logits).all()),
        "serve_tier": kv_stats,
        "traffic": ledger.as_dict(),
    }
    print(json.dumps(out, indent=2))
    out["layer_kv"] = layer_kv      # the model KV the tier mirrored
    return out


if __name__ == "__main__":
    main()
