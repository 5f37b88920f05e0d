"""On-device value streams keyed by (seed, session, position), for the
attention kinds' generators (`bench/kinds/`).

The distribution is the one of `repro.kv.traffic.synthetic_kv_stream`,
made on the device: compressible values hover multiplicatively (`scale`)
around a per-session base 2 + 0.2 N, shared by the token's leading axes
(K and V, where a kind feeds both), so bf16 pages delta-pack; unit-normal
values never fit the int8 or int4 deltas.  Every value is a function of
(seed, session uid, position) alone, so the reference regenerates exactly
what a session was fed, whatever the block sizes the feed and the
regeneration used.  The helpers here are traced inside a kind's jitted
generator; a kind chooses only the shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KV_TAG, Q_TAG, BASE_TAG = 1, 2, 3


def root_key(seed: int):
    """A PRNG key from any whole seed below 2**62 (seeds may exceed
    32 signed bits)."""
    seed = int(seed)
    assert 0 <= seed < 1 << 62, seed
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def stream(root, uid, start, *, t, shape, base_shape, dist, scale=0.0):
    """(t, *shape) bf16: session `uid`'s values at positions start + [0,
    t).  `base_shape`, the trailing axes of `shape`, is what the
    compressible base varies over; the leading axes share it."""
    sk = jax.random.fold_in(jax.random.fold_in(root, KV_TAG), uid)
    pos = start + jnp.arange(t, dtype=jnp.int32)
    noise = jax.vmap(lambda p: jax.random.normal(
        jax.random.fold_in(sk, p), shape, jnp.float32))(pos)
    if dist == "compressible":
        bk = jax.random.fold_in(jax.random.fold_in(root, BASE_TAG), uid)
        base = 2.0 + 0.2 * jax.random.normal(bk, base_shape, jnp.float32)
        x = base * (1.0 + noise * scale)
    elif dist == "unit_normal":
        x = noise
    else:
        raise ValueError(f"unknown KV distribution {dist!r}")
    return x.astype(jnp.bfloat16)


def query(root, uid, pos, shape):
    """float32 unit normals of `shape`: session `uid`'s query at position
    `pos` (the newest token's)."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(root, Q_TAG), uid), pos)
    return jax.random.normal(key, shape, jnp.float32)
