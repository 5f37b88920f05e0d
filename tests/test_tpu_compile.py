"""Described-chip compiles of the main-path kernels for a TPU v5e.

Each test lowers one kernel entry at serving width (head_dim 128, page 16,
8 KV heads) for a v5e that is described, not attached, and compiles it
with the TPU compiler.  That catches what interpret mode cannot: blocks
that break the TPU tiling rule, ops Mosaic cannot lower, and kernels that
overrun the scoped VMEM.  Nothing runs.

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.  The persistent compilation cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.compress_scan import BLOCK_LINES, WORDS_PER_LINE, _scan_call
from repro.kernels.prefill_pack import prefill_pack
from repro.kernels.ref import MARKER_LANES

PAGE, HKV, HD, HQ = 16, 8, 128, 24      # phi4-mini's KV geometry
D2 = 2 * HD


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("lanes", [2, 4])
def test_window_pack_compiles(one_chip, lanes):
    b, w = 2, 4
    win = _spec(one_chip, (b, w, lanes, PAGE, HKV, D2), jnp.int16)
    mk = _spec(one_chip, (w, MARKER_LANES), jnp.int16)
    en = _spec(one_chip, (b,), jnp.bool_)
    text = _compiled_text(
        lambda x, m, e: kops.layout_window(x, m, e, use_pack=True,
                                           interpret=False), win, mk, en)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lanes", [2, 4])
def test_prefill_pack_compiles(one_chip, lanes):
    b, n_groups, w = 2, 8, 4
    pages = _spec(one_chip, (b, n_groups * lanes * PAGE, HKV, D2), jnp.int16)
    idx = _spec(one_chip, (w,), jnp.int32)
    mk = _spec(one_chip, (n_groups, MARKER_LANES), jnp.int16)
    en = _spec(one_chip, (b,), jnp.bool_)
    text = _compiled_text(
        lambda p, i, m, e: prefill_pack(p, i, m, e, lanes=lanes, page=PAGE,
                                        interpret=False), pages, idx, mk, en)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lanes", [2, 4])
def test_batched_decode_compiles(one_chip, lanes):
    b, n = 4, 8                           # n page groups per sequence
    over = ((b, n, PAGE, HKV, D2) if lanes == 2
            else (b, n, lanes - 1, PAGE, HKV, D2))
    cache = {
        "slots": _spec(one_chip, (b, n, PAGE, HKV, D2), jnp.int16),
        "slots_overflow": _spec(one_chip, over, jnp.int16),
        "strips": _spec(one_chip, (b, n, HKV, D2 + MARKER_LANES), jnp.int16),
        "packed_mask": _spec(one_chip, (b, n), jnp.bool_),
        "markers": _spec(one_chip, (n,), jnp.int32),
    }
    q = _spec(one_chip, (b, HQ, HD), jnp.float32)
    valid = _spec(one_chip, (b, lanes * n), jnp.int32)
    pred = _spec(one_chip, (b, n), jnp.bool_)
    text = _compiled_text(
        lambda q, c, v, p: kops.decode_attention_fused(
            q, c, v, p, lanes=lanes, interpret=False), q, cache, valid, pred)
    assert "tpu_custom_call" in text


def test_compress_scan_compiles(one_chip):
    words = _spec(one_chip, (4096, WORDS_PER_LINE), jnp.int32)
    text = _compiled_text(
        lambda w: _scan_call(w, key=12345, block=BLOCK_LINES, interpret=False), words)
    assert "tpu_custom_call" in text
