"""On-device KV and query generator, keyed by (seed, session, position).

The distribution is the one of `repro.kv.traffic.synthetic_kv_stream`,
made on the device: compressible KV hovers multiplicatively (`scale`)
around a per-(session, KV head, dim) base 2 + 0.2 N, shared by K and V,
so bf16 pages delta-pack; unit-normal KV never fits the int8 or int4
deltas.  Every value is a function of (seed, session uid, position)
alone, so the reference regenerates exactly what a session was fed,
whatever the block sizes the feed and the regeneration used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KV_TAG, Q_TAG, BASE_TAG = 1, 2, 3


def root_key(seed: int):
    """A PRNG key from any whole seed below 2**62 (seeds may exceed
    32 signed bits)."""
    seed = int(seed)
    assert 0 <= seed < 1 << 62, seed
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnames=("t", "n_kv", "d", "dist",
                                             "scale"))
def kv_tokens(root, uids, starts, *, t, n_kv, d, dist, scale=0.0):
    """k, v (S, t, n_kv, d) bf16 for sessions `uids` (S,) at positions
    starts[s] + [0, t)."""
    def one(uid, start):
        sk = jax.random.fold_in(jax.random.fold_in(root, KV_TAG), uid)
        pos = start + jnp.arange(t, dtype=jnp.int32)
        noise = jax.vmap(lambda p: jax.random.normal(
            jax.random.fold_in(sk, p), (2, n_kv, d), jnp.float32))(pos)
        if dist == "compressible":
            bk = jax.random.fold_in(jax.random.fold_in(root, BASE_TAG), uid)
            base = 2.0 + 0.2 * jax.random.normal(bk, (n_kv, d), jnp.float32)
            kv = base * (1.0 + noise * scale)
        elif dist == "unit_normal":
            kv = noise
        else:
            raise ValueError(f"unknown KV distribution {dist!r}")
        kv = kv.astype(jnp.bfloat16)
        return kv[:, 0], kv[:, 1]
    return jax.vmap(one)(jnp.asarray(uids, jnp.int32),
                         jnp.asarray(starts, jnp.int32))


@functools.partial(jax.jit, static_argnames=("hq", "d"))
def queries(root, uids, positions, *, hq, d):
    """q (S, hq, d) float32: the query of session uids[s] at position
    positions[s] (the newest token's)."""
    def one(uid, pos):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(root, Q_TAG), uid), pos)
        return jax.random.normal(key, (hq, d), jnp.float32)
    return jax.vmap(one)(jnp.asarray(uids, jnp.int32),
                         jnp.asarray(positions, jnp.int32))


@functools.partial(jax.jit, static_argnames=("t", "n_kv", "d", "dist",
                                             "scale"))
def kv_split(root, uids, starts, *, t, n_kv, d, dist, scale=0.0):
    """`kv_tokens` as one tuple of per-session (t, n_kv, d) arrays for k
    and one for v: the per-session arrays a step takes, in one dispatch."""
    k, v = kv_tokens(root, uids, starts, t=t, n_kv=n_kv, d=d, dist=dist,
                     scale=scale)
    return (tuple(k[i] for i in range(k.shape[0])),
            tuple(v[i] for i in range(v.shape[0])))


@functools.partial(jax.jit, static_argnames=("hq", "d"))
def queries_split(root, uids, positions, *, hq, d):
    """`queries` as a tuple of per-session (hq, d) arrays."""
    q = queries(root, uids, positions, hq=hq, d=d)
    return tuple(q[i] for i in range(q.shape[0]))


class Generator:
    """Binds the generator to one run's seed and one cell's geometry."""

    def __init__(self, seed: int, *, n_kv: int, d: int, hq: int, kv: dict):
        self.root = root_key(seed)
        self.n_kv, self.d, self.hq = n_kv, d, hq
        self.dist = kv["dist"]
        self.scale = float(kv.get("scale", 0.0))

    def kv(self, uids, starts, t: int):
        uids, starts = np.asarray(uids, np.int32), np.asarray(starts, np.int32)
        return kv_tokens(self.root, uids, starts, t=t, n_kv=self.n_kv,
                         d=self.d, dist=self.dist, scale=self.scale)

    def q(self, uids, positions):
        return queries(self.root, np.asarray(uids, np.int32),
                       np.asarray(positions, np.int32), hq=self.hq, d=self.d)

    def kv_each(self, uids, starts, t: int):
        """Per-session k and v tuples (one dispatch)."""
        return kv_split(self.root, np.asarray(uids, np.int32),
                        np.asarray(starts, np.int32), t=t, n_kv=self.n_kv,
                        d=self.d, dist=self.dist, scale=self.scale)

    def q_each(self, uids, positions):
        """Per-session query tuple (one dispatch)."""
        return queries_split(self.root, np.asarray(uids, np.int32),
                             np.asarray(positions, np.int32), hq=self.hq,
                             d=self.d)

    def session_kv(self, uid: int, length: int, chunk: int = 512):
        """k, v (length, n_kv, d) bf16: positions [0, length) of one
        session, made in fixed chunks so one program serves every
        length."""
        ks, vs = [], []
        for c0 in range(0, length, chunk):
            k, v = self.kv([uid], [c0], chunk)
            ks.append(k[0])
            vs.append(v[0])
        return (jnp.concatenate(ks)[:length], jnp.concatenate(vs)[:length])
