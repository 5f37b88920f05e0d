"""Grouped-query attention (GQA), the kind of every configuration that
names none.

A token's cache payload is a (k, v) pair of (n_kv, d) each; a query is
(Hq, d) float32, and query head h attends KV head h // (Hq // n_kv).  The
widths come from the configuration: `num_key_value_heads`,
`num_attention_heads` and `assumed.head_dim`.

What the harness asks of a kind (`bench/harness/spec.py`, `Cell.kind`):
  make_loop(config, plan, serve)   the cell's ServeLoop
  Generator(seed, config, mix)     payloads, queries and the reference's
                                   state, keyed by (seed, session, position)
  reference_answers(ref, q, state, lengths, kv_dtype=None)
                                   the plain reference's answers
  geometry(config, loop)           what the cache moves and computes, for
                                   `harness.work`
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from harness import kvgen, work


def _widths(config: dict) -> tuple:
    """(n_kv, d, hq)."""
    return (int(config["num_key_value_heads"]),
            int(config["assumed"]["head_dim"]),
            int(config["num_attention_heads"]))


def make_loop(config: dict, plan, serve: dict):
    """The serve tier at its defaults, or as the mix's "serve" entry sets
    it (e.g. {"packing": "quad"})."""
    from repro.serving import ServeLoop

    n_kv, d, _ = _widths(config)
    page = int(config["assumed"]["page"])
    return ServeLoop(slots=plan.slots, max_pages=plan.capacity // page,
                     page=page, n_kv=n_kv, head_dim=d, **serve)


# ------------------------------------------------------------- generator
@functools.partial(jax.jit, static_argnames=("t", "n_kv", "d", "dist",
                                             "scale"))
def kv_tokens(root, uids, starts, *, t, n_kv, d, dist, scale=0.0):
    """k, v (S, t, n_kv, d) bf16 for sessions `uids` (S,) at positions
    starts[s] + [0, t)."""
    def one(uid, start):
        kv = kvgen.stream(root, uid, start, t=t, shape=(2, n_kv, d),
                          base_shape=(n_kv, d), dist=dist, scale=scale)
        return kv[:, 0], kv[:, 1]
    return jax.vmap(one)(jnp.asarray(uids, jnp.int32),
                         jnp.asarray(starts, jnp.int32))


@functools.partial(jax.jit, static_argnames=("hq", "d"))
def queries(root, uids, positions, *, hq, d):
    """q (S, hq, d) float32: the query of session uids[s] at position
    positions[s]."""
    return jax.vmap(lambda uid, pos: kvgen.query(root, uid, pos, (hq, d)))(
        jnp.asarray(uids, jnp.int32), jnp.asarray(positions, jnp.int32))


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
    """Binds the streams to one run's seed and one cell's widths."""

    def __init__(self, seed: int, config: dict, mix: dict):
        self.root = kvgen.root_key(seed)
        self.n_kv, self.d, self.hq = _widths(config)
        self.dist = mix["kv"]["dist"]
        self.scale = float(mix["kv"].get("scale", 0.0))

    def kv(self, uids, starts, t: int):
        """k, v (S, t, n_kv, d) bf16."""
        uids, starts = np.asarray(uids, np.int32), np.asarray(starts, np.int32)
        return kv_tokens(self.root, uids, starts, t=t, n_kv=self.n_kv,
                         d=self.d, dist=self.dist, scale=self.scale)

    def q(self, uids, positions):
        """q (S, hq, d) float32, for the reference."""
        return queries(self.root, np.asarray(uids, np.int32),
                       np.asarray(positions, np.int32), hq=self.hq, d=self.d)

    def feed_each(self, uids, starts, t: int) -> list:
        """Per session, the (k, v) that `loop.prefill(uid, k, v)` and
        `loop.step({uid: (k, v)})` take (one dispatch)."""
        k, v = kv_split(self.root, np.asarray(uids, np.int32),
                        np.asarray(starts, np.int32), t=t, n_kv=self.n_kv,
                        d=self.d, dist=self.dist, scale=self.scale)
        return list(zip(k, v, strict=True))

    def q_each(self, uids, positions):
        """Per-session query tuple for `loop.attend` (one dispatch)."""
        return queries_split(self.root, np.asarray(uids, np.int32),
                             np.asarray(positions, np.int32), hq=self.hq,
                             d=self.d)

    def session_state(self, uid: int, length: int, chunk: int = 512):
        """k, v (length, n_kv, d) bf16: positions [0, length) of one
        session, made in fixed chunks so one program serves every
        length."""
        ks, vs = [], []
        for c0 in range(0, length, chunk):
            k, v = self.kv([uid], [c0], chunk)
            ks.append(k[0])
            vs.append(v[0])
        return (jnp.concatenate(ks)[:length], jnp.concatenate(vs)[:length])


def reference_answers(ref, q, state, lengths, kv_dtype=None):
    """(P, Hq, d) float32 from the plain GQA reference."""
    k, v = state
    return ref.attend(q, k, v, lengths, kv_dtype=kv_dtype)


# ------------------------------------------------------------------ work
@dataclass(frozen=True)
class Geometry:
    page: int
    lanes: int
    n_kv: int
    d: int
    hq: int

    @property
    def slot_bytes(self) -> int:        # one page of K|V, bf16
        return self.page * self.n_kv * 2 * self.d * 2

    @property
    def strip_bytes(self) -> int:       # base row + two marker lanes, int16
        return self.n_kv * (2 * self.d + 2) * 2

    @property
    def token_bytes(self) -> int:       # one appended token's K and V, bf16
        return 2 * self.n_kv * self.d * 2

    def seq_bytes(self, tokens: int, packed_share: float) -> float:
        return work.paged_bytes(tokens, packed_share, page=self.page,
                                lanes=self.lanes,
                                slot_bytes=self.slot_bytes + self.strip_bytes)

    def attend_flops(self, tokens: int) -> int:
        """QK^T and PV over `tokens` positions for every query head."""
        return 4 * self.hq * self.d * tokens


def geometry(config: dict, loop) -> Geometry:
    n_kv, d, hq = _widths(config)
    return Geometry(int(config["assumed"]["page"]), loop.cache.group_lanes,
                    n_kv, d, hq)
