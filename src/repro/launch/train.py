"""Training launcher (real execution on the local devices).

For the production mesh this is the same step function the dry-run
AOT-compiles; on the CPU container it runs reduced configs end-to-end with
the full substrate engaged: synthetic data pipeline, AdamW(+ZeRO specs),
remat, microbatching, fault-tolerant checkpoint/restart loop, straggler
detection, and optional CRAM-compressed checkpoints.

  python -m repro.launch.train --arch qwen3_8b --smoke --steps 200
  python -m repro.launch.train --preset lm20m --steps 300 --inject-fault 120
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..data import DataConfig, make_batch_iterator
from ..models import ModelConfig, build, count_params, smoke_config
from ..optim.adamw import adamw_init, make_train_step
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.ft import LoopConfig, SimulatedFault, run_with_restarts

PRESETS = {
    # ~20M-param LM for the e2e example (trains visibly in minutes on CPU)
    "lm20m": ModelConfig(
        name="lm20m", family="dense", n_layers=4, d_model=384, n_heads=6,
        n_kv_heads=6, head_dim=64, d_ff=1024, vocab=8192, max_seq=256,
        microbatches=1, remat=False, attn_q_chunk=128, attn_k_chunk=128,
        xent_chunk=128, dtype=jnp.float32, param_dtype=jnp.float32),
    "lm2m": ModelConfig(
        name="lm2m", family="dense", n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=4, head_dim=32, d_ff=256, vocab=2048, max_seq=128,
        microbatches=1, remat=False, attn_q_chunk=64, attn_k_chunk=64,
        xent_chunk=64, dtype=jnp.float32, param_dtype=jnp.float32),
}


def build_config(args) -> ModelConfig:
    if args.preset:
        return PRESETS[args.preset]
    cfg = configs.get(configs.canonical(args.arch))
    return smoke_config(cfg) if args.smoke else cfg


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", default=None, choices=[*PRESETS, None])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--codec", default="cram")
    ap.add_argument("--inject-fault", type=int, default=0,
                    help="raise a SimulatedFault once at this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = build_config(args)
    seq = args.seq or min(cfg.max_seq, 256)
    model = build(cfg)
    print(f"training {cfg.name}: {count_params(cfg)/1e6:.1f}M params, "
          f"batch {args.batch} x seq {seq}")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=args.batch,
                      seed=args.seed, family=cfg.family,
                      d_model=cfg.d_model,
                      n_image_tokens=cfg.n_image_tokens)

    def make_state():
        params, _ = model.init(jax.random.key(args.seed))
        return adamw_init(params, cfg.optimizer_dtype)

    def make_step_fn():
        return jax.jit(make_train_step(model, lr_peak=args.lr,
                                       lr_total=args.steps))

    def make_batch_iter(start_step):
        it = make_batch_iterator(dcfg, start_step=start_step)
        return it

    fired = {"done": False}

    def injector(step):
        if args.inject_fault and step == args.inject_fault \
                and not fired["done"]:
            fired["done"] = True
            raise SimulatedFault(f"injected at step {step}")

    loop_cfg = LoopConfig(total_steps=args.steps,
                          ckpt_every=args.ckpt_every,
                          ckpt_dir=args.ckpt_dir, codec=args.codec)
    t0 = time.time()
    res, state = run_with_restarts(
        make_step_fn, make_state, make_batch_iter, loop_cfg,
        fault_injector=injector if args.inject_fault else None)
    wall = time.time() - t0
    first = float(np.mean(res.losses[:10]))
    last = float(np.mean(res.losses[-10:]))
    out = {
        "name": cfg.name, "steps": res.final_step, "wall_s": round(wall, 1),
        "loss_first10": round(first, 4), "loss_last10": round(last, 4),
        "restarts": res.restarts,
        "straggler_flags": len(res.straggler_flags),
        "mean_step_ms": round(1e3 * float(np.mean(res.step_times)), 1),
    }
    print(json.dumps(out, indent=2))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({**out, "losses": res.losses}, f)
    return out


if __name__ == "__main__":
    main()
