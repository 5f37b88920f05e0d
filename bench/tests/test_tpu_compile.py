"""Each cell's step programs compile at full size for a described TPU
v5e (no chip needed), and fit its memory.

The fused megastep and the attend program are lowered with the shapes
the cell's window drives, on one described chip, and
`memory_analysis()` gives the bytes each program needs.  Run with
`-s` to print them.  A compile that passes is not a chip run."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import CHAT_CELL, chat_benchmark

from harness import spec
from harness.traffic import make_plan

HBM = 16 * 2**30

CELLS = ["phi4_mini.longctx.compressible", CHAT_CELL["name"]]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _specs(cell_name, sharding):
    """Full-size shapes of the pool state and a decode step's inputs,
    scaled from a two-slot pool of the same geometry."""
    from repro.serving.slots import SlotKVCache

    cell = spec.load(cell_name, chat_benchmark()
                     if cell_name == CHAT_CELL["name"] else None)
    cfg = cell.config
    page, d = cfg["assumed"]["page"], cfg["assumed"]["head_dim"]
    n_kv, hq = cfg["num_key_value_heads"], cfg["num_attention_heads"]
    plan = make_plan(cell.mix, 0)
    small = SlotKVCache(8, page, n_kv, d, batch=2, interpret=False)
    b, n = plan.slots, plan.capacity // page // small.group_lanes
    lanes = small.group_lanes

    def full(key, x):
        shp = list(x.shape)
        if key == "pages":
            shp[:2] = [b, n * lanes * page]
        elif key == "markers":
            shp[0] = n
        elif key in ("slots", "slots_overflow", "strips", "packed_mask",
                     "predictor"):
            shp[:2] = [b, n]
        elif key in ("counter", "pred_hits", "pred_misses"):
            shp[0] = b
        return jax.ShapeDtypeStruct(tuple(shp), x.dtype, sharding=sharding)

    state = {k: full(k, v) for k, v in small.state.items()}
    mk = jax.ShapeDtypeStruct((n, 2), jnp.int16, sharding=sharding)
    return cell, plan, state, mk, dict(b=b, n=n, lanes=lanes, page=page,
                                       n_kv=n_kv, d=d, hq=hq,
                                       slot_bytes=small.slot_bytes,
                                       strip_bytes=small.strip_bytes)


def _s(shape, dtype, sh):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


@pytest.mark.parametrize("cell_name", CELLS)
def test_megastep_and_attend_fit(cell_name, one_chip):
    from repro.kernels import ops as kops
    from repro.kv.cache import kernel_cache_slice
    from repro.serving.slots import _megastep

    _, plan, state, mk, g = _specs(cell_name, one_chip)
    b, n, lanes = g["b"], g["n"], g["lanes"]
    s_named = plan.slots            # a full wave
    wb = 4 if plan.endless else 32  # the cell's widest usual window
    sh = one_chip
    mega = _megastep.lower(
        state, mk, _s((s_named, 1, g["n_kv"], g["d"]), jnp.bfloat16, sh),
        _s((s_named, 1, g["n_kv"], g["d"]), jnp.bfloat16, sh),
        _s((s_named,), jnp.int32, sh), _s((b,), jnp.int32, sh),
        _s((b,), jnp.bool_, sh), _s((wb,), jnp.int32, sh),
        _s((b,), jnp.bool_, sh), _s((b, wb), jnp.bool_, sh),
        _s((b, lanes * n), jnp.int32, sh), lanes=lanes,
        slot_bytes=g["slot_bytes"], strip_bytes=g["strip_bytes"],
        use_pack=True, dyn=True, interpret=False).compile()
    kc = jax.eval_shape(lambda st: kernel_cache_slice(st, n), state)
    kc = {k: _s(v.shape, v.dtype, sh) for k, v in kc.items()}
    att = kops.decode_attention_fused.lower(
        _s((b, g["hq"], g["d"]), jnp.float32, sh), kc,
        _s((b, lanes * n), jnp.int32, sh), lanes=lanes,
        interpret=False).compile()
    report = {}
    for name, c in (("megastep", mega), ("attend", att)):
        m = c.memory_analysis()
        tot = (m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)
        report[name] = {"arguments": m.argument_size_in_bytes,
                        "outputs": m.output_size_in_bytes,
                        "temporaries": m.temp_size_in_bytes,
                        "aliased": m.alias_size_in_bytes, "total": tot}
        assert "tpu_custom_call" in c.as_text(), name
        assert tot < HBM, (name, report[name])
    print(cell_name, json.dumps(report))
