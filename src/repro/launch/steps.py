"""Step functions + sharding trees shared by dryrun.py / train.py / serve.py."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import ShapeSpec, build, input_specs
from ..optim.adamw import abstract_opt_state, make_train_step
from ..runtime.sharding import (
    RuleSet,
    spec_for,
    tree_shardings,
    zero_shardings,
)

# logical axes for model inputs, by name
INPUT_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "frames": ("batch", "seq", "embed"),
    "image_embeds": ("batch", "image", "embed"),
    "token": ("batch", None),
    "index": (),
}

# logical axes for decode-cache leaves, by leaf name
CACHE_AXES = {
    "k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    "xk": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    "xv": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    "conv_x": ("layers", "batch", None, "mlp"),
    "conv_B": ("layers", "batch", None, None),
    "conv_C": ("layers", "batch", None, None),
    "h": ("layers", "batch", "heads", None, None),
}

# whisper caches lack the stacked 'layers' handling difference: same names


def batch_shardings(cfg, shape: ShapeSpec, mesh, rules: RuleSet):
    specs = input_specs(cfg, shape)
    return {
        k: NamedSharding(mesh, spec_for(INPUT_AXES[k], v.shape, mesh, rules))
        for k, v in specs.items()
    }


def cache_shardings(cache_shapes, mesh, rules: RuleSet):
    def leaf_sharding(path, leaf):
        name = None
        for entry in reversed(path):
            if hasattr(entry, "key"):
                name = entry.key
                break
        axes = CACHE_AXES.get(name, tuple([None] * len(leaf.shape)))
        if len(axes) != len(leaf.shape):
            axes = tuple([None] * len(leaf.shape))
        return NamedSharding(mesh, spec_for(axes, leaf.shape, mesh, rules))

    return jax.tree_util.tree_map_with_path(leaf_sharding, cache_shapes)


def make_prefill_step(model):
    def prefill_step(params, batch):
        h = model.forward(params, batch)
        # last-position logits only (next-token after the prompt)
        from ..models.layers import logits_last

        return logits_last(h[:, -1], params["embed"])

    return prefill_step


def make_serve_step(model):
    cfg = model.config

    def serve_step(params, token, cache, index, image_embeds=None):
        kw = {}
        if cfg.family == "vlm":
            kw["image_embeds"] = image_embeds
        logits, cache = model.decode_step(params, token, cache, index, **kw)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_token, cache, logits

    return serve_step


def build_cell(arch_cfg, shape: ShapeSpec, mesh, rules: RuleSet | None = None,
               *, fsdp: bool = True):
    """Everything needed to lower one (arch x shape x mesh) cell abstractly.

    Returns (fn, arg_shapes tuple, in_shardings tuple, out_shardings).
    """
    rules = rules or RuleSet()
    model = build(arch_cfg)
    pshapes, paxes = model.abstract_params()
    # FSDP/ZeRO-3 style (default): params are TP-sharded on `model` AND
    # additionally sharded over `data` on their largest replicated dim; XLA
    # all-gathers per layer.  This is what lets 123B/400B cells fit
    # 16GB/chip.  fsdp=False keeps TP-only params (no per-layer gathers) —
    # the right call for models whose weights fit, see §Perf.
    if fsdp:
        pshard = zero_shardings(paxes, pshapes, mesh, rules)
    else:
        pshard = tree_shardings(paxes, pshapes, mesh, rules)
    bshard = batch_shardings(arch_cfg, shape, mesh, rules)
    bshapes = input_specs(arch_cfg, shape)

    if shape.kind == "train":
        from ..optim.adamw import TrainState

        state_shapes = abstract_opt_state(pshapes, arch_cfg.optimizer_dtype)
        repl = NamedSharding(mesh, P())
        zshard = zero_shardings(paxes, pshapes, mesh, rules)
        state_shard = TrainState(params=pshard, m=zshard, v=zshard,
                                 step=repl, dyn_counter=repl)
        fn = make_train_step(model)
        return fn, (state_shapes, bshapes), (state_shard, bshard), None

    if shape.kind == "prefill":
        fn = make_prefill_step(model)
        return fn, (pshapes, bshapes), (pshard, bshard), None

    # decode
    B, T = shape.global_batch, shape.seq_len
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, T))
    cshard = cache_shardings(cache_shapes, mesh, rules)
    repl = NamedSharding(mesh, P())
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_shard = NamedSharding(
        mesh, spec_for(("batch", None), (B, 1), mesh, rules))
    index = jax.ShapeDtypeStruct((), jnp.int32)
    fn = make_serve_step(model)
    args = [pshapes, token, cache_shapes, index]
    shards = [pshard, tok_shard, cshard, repl]
    if arch_cfg.family == "vlm":
        img = jax.ShapeDtypeStruct(
            (B, arch_cfg.n_image_tokens, arch_cfg.d_model), arch_cfg.dtype)
        args.append(img)
        shards.append(NamedSharding(
            mesh, spec_for(INPUT_AXES["image_embeds"], img.shape, mesh,
                           rules)))
    return fn, tuple(args), tuple(shards), None
