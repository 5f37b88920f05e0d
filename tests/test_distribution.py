"""Distribution tests on 8 fake CPU devices (subprocess-isolated: jax locks
the device count at first init, so each scenario runs in its own python).

Covers: pjit train step under the sharding rules (DP x TP), decode with a
sequence-sharded KV cache (SP), GPipe pipeline == sequential forward, the
shard_map compressed-gradient DP step, and a miniature dry-run lowering."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str) -> dict:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ.pop("JAX_PLATFORMS", None)
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import AxisType
        assert len(jax.devices()) == 8

        def auto_mesh(shape, names):
            # the tests drive sharding through `with mesh:` and rules, the
            # auto-sharding mode; make_mesh defaults to explicit axes
            return jax.make_mesh(shape, names,
                                 axis_types=(AxisType.Auto,) * len(shape))
    """) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_pjit_train_step_dp_tp():
    out = run_py("""
        from jax.sharding import Mesh
        from repro.launch.train import PRESETS
        from repro.launch.steps import build_cell
        from repro.models import build, ShapeSpec, input_specs
        from repro.optim.adamw import adamw_init, make_train_step
        from repro.runtime.sharding import RuleSet, tree_shardings, activation_sharding

        mesh = auto_mesh((4, 2), ("data", "model"))
        cfg = PRESETS["lm2m"]
        model = build(cfg)
        rules = RuleSet()
        params, axes = model.init(jax.random.key(0))
        shards = tree_shardings(axes, jax.eval_shape(lambda: params), mesh, rules)
        params = jax.device_put(params, shards)
        state = adamw_init(params)
        step = jax.jit(make_train_step(model, microbatches=2))
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab),
            "labels": jax.random.randint(jax.random.key(2), (8, 64), 0, cfg.vocab),
        }
        losses = []
        with mesh, activation_sharding(mesh, rules):
            for _ in range(8):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
        assert losses[-1] < losses[0], losses  # lr warms up over steps
        print(json.dumps({"loss": losses[-1]}))
    """)
    assert out["loss"] > 0


def test_sp_decode_kv_sharded_matches_single_device():
    out = run_py("""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.models.attention import chunked_decode_attention

        mesh = auto_mesh((8, 1), ("data", "model"))
        B, T, Hq, Hkv, D = 2, 256, 4, 2, 16
        q = jax.random.normal(jax.random.key(0), (B, Hq, D))
        k = jax.random.normal(jax.random.key(1), (B, T, Hkv, D))
        v = jax.random.normal(jax.random.key(2), (B, T, Hkv, D))
        ref = chunked_decode_attention(q, k, v, length=199, k_chunk=32)
        kv_shard = NamedSharding(mesh, P(None, "data"))
        k_s = jax.device_put(k, kv_shard)
        v_s = jax.device_put(v, kv_shard)
        with mesh:
            out = jax.jit(lambda q, k, v: chunked_decode_attention(
                q, k, v, length=199, k_chunk=32))(q, k_s, v_s)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 1e-4


def test_gpipe_matches_sequential():
    out = run_py("""
        from repro.runtime.pipeline import gpipe_apply, split_stages
        mesh = auto_mesh((4, 2), ("stage", "data"))
        L, D = 8, 32
        key = jax.random.key(0)
        Ws = jax.random.normal(key, (L, D, D)) * 0.2

        def layer(w, x):
            return jnp.tanh(x @ w)

        def stage_fn(w_stage, x):
            for i in range(w_stage.shape[0]):
                x = layer(w_stage[i], x)
            return x

        M, mb, S = 4, 2, 8
        x = jax.random.normal(jax.random.key(1), (M, mb, S, D))
        seq = x
        for i in range(L):
            seq = layer(Ws[i], seq)
        stages = split_stages(Ws, 4)
        outp = gpipe_apply(stages, x, mesh=mesh, stage_fn=stage_fn)
        err = float(jnp.max(jnp.abs(outp - seq)))
        assert err < 1e-5, err
        # gradients flow through the pipeline
        def loss(ws):
            return jnp.sum(gpipe_apply(split_stages(ws, 4), x, mesh=mesh,
                                       stage_fn=stage_fn) ** 2)
        g = jax.grad(loss)(Ws)
        gn = float(jnp.linalg.norm(g))
        assert np.isfinite(gn) and gn > 0
        print(json.dumps({"err": err, "gnorm": gn}))
    """)
    assert out["err"] < 1e-5


def test_dp_compressed_gradients():
    out = run_py("""
        from repro.launch.train import PRESETS
        from repro.models import build
        from repro.optim import grad_compress as gc

        mesh = auto_mesh((8,), ("data",))
        cfg = PRESETS["lm2m"]
        model = build(cfg)
        params, _ = model.init(jax.random.key(0))
        step = gc.make_dp_compressed_step(model, mesh, lr=5e-3)
        err = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        counter = jnp.int32(gc.ENABLE + 64)
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab),
            "labels": jax.random.randint(jax.random.key(2), (8, 32), 0, cfg.vocab),
        }
        losses = []
        for _ in range(6):
            params, err, counter, loss = step(params, err, counter, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        print(json.dumps({"first": losses[0], "last": losses[-1],
                          "enabled": bool(counter >= gc.ENABLE)}))
    """)
    assert out["last"] < out["first"]


def test_mini_dryrun_lowering():
    out = run_py("""
        from repro.configs import get_smoke
        from repro.launch.steps import build_cell
        from repro.models import ShapeSpec
        from repro.runtime.sharding import RuleSet, activation_sharding
        from repro.launch.hlo_analysis import analyze_compiled

        mesh = auto_mesh((4, 2), ("data", "model"))
        cfg = get_smoke("olmoe_1b_7b")
        spec = ShapeSpec("mini", 128, 8, "train")
        fn, shapes, shards, _ = build_cell(cfg, spec, mesh, RuleSet())
        with mesh, activation_sharding(mesh, RuleSet()):
            compiled = jax.jit(fn, in_shardings=shards).lower(*shapes).compile()
        info = analyze_compiled(compiled)
        assert info["flops"] > 0
        assert info["collectives"]["total_ops"] > 0
        print(json.dumps({"flops": info["flops"],
                          "colls": info["collectives"]["total_ops"]}))
    """)
    assert out["colls"] > 0
