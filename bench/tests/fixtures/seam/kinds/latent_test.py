"""A test attention kind that the harness does not know: a latent cache in
the manner of multi-head latent attention, at tiny widths.

A token's payload is one array, its latent row [c, k_rope] of
r + dr values (`kv_lora_rank`, `qk_rope_head_dim`), shared by all H
query heads.  The loop holds the row as the K and the V of one KV head
(V is its first r lanes, so the answer is the attend output's first r
lanes).  Queries come in two parts per head, q_nope (dn wide) and q_rope;
the loop is given them absorbed, [q_nope[h] W_UK[h], q_rope[h]], with a
weight W_UK (H, dn, r) made from the seed, and the reference gets the
weight in the session's state and makes the keys from the latent itself.
The loop's kernel scales scores by 1 / sqrt(r + dr), and so does the
reference.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from harness import kvgen, work

W_TAG = 4


def _widths(config: dict) -> tuple:
    """(H, dn, r, dr)."""
    return (int(config["num_attention_heads"]),
            int(config["qk_nope_head_dim"]), int(config["kv_lora_rank"]),
            int(config["qk_rope_head_dim"]))


class LatentLoop:
    """The serve loop fed one latent row per token."""

    def __init__(self, inner, rank: int):
        self.inner, self.rank = inner, rank

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def prefill(self, seq_id, rows):
        return self.inner.prefill(seq_id, rows, rows)

    def step(self, feed_by_seq: dict) -> dict:
        return self.inner.step({s: (rows, rows)
                                for s, (rows,) in feed_by_seq.items()})

    def attend(self, q_by_seq: dict, **kw) -> dict:
        out = self.inner.attend(q_by_seq, **kw)
        return {s: o[:, :self.rank] for s, o in out.items()}


def make_loop(config: dict, plan, serve: dict):
    from repro.serving import ServeLoop

    _, _, r, dr = _widths(config)
    page = int(config["assumed"]["page"])
    return LatentLoop(ServeLoop(slots=plan.slots,
                                max_pages=plan.capacity // page, page=page,
                                n_kv=1, head_dim=r + dr, **serve), r)


@functools.partial(jax.jit, static_argnames=("t", "width", "dist", "scale"))
def rows_split(root, uids, starts, *, t, width, dist, scale=0.0):
    """Per-session (t, 1, width) bf16 latent rows, in one dispatch."""
    rows = jax.vmap(lambda u, s: kvgen.stream(
        root, u, s, t=t, shape=(1, width), base_shape=(1, width), dist=dist,
        scale=scale))(jnp.asarray(uids, jnp.int32),
                      jnp.asarray(starts, jnp.int32))
    return tuple(rows[i] for i in range(rows.shape[0]))


@functools.partial(jax.jit, static_argnames=("h", "dn", "dr"))
def queries(root, uids, positions, *, h, dn, dr):
    """(q_nope (S, H, dn), q_rope (S, H, dr)) float32."""
    q = jax.vmap(lambda u, p: kvgen.query(root, u, p, (h, dn + dr)))(
        jnp.asarray(uids, jnp.int32), jnp.asarray(positions, jnp.int32))
    return q[..., :dn], q[..., dn:]


@jax.jit
def absorbed_split(q_nope, q_rope, w_uk):
    """Per-session (H, r + dr) absorbed queries."""
    qa = jnp.concatenate([jnp.einsum("shn,hnr->shr", q_nope, w_uk,
                                     precision=jax.lax.Precision.HIGHEST),
                          q_rope], axis=-1)
    return tuple(qa[i] for i in range(qa.shape[0]))


class Generator:
    def __init__(self, seed: int, config: dict, mix: dict):
        self.root = kvgen.root_key(seed)
        self.h, self.dn, self.r, self.dr = _widths(config)
        self.dist = mix["kv"]["dist"]
        self.scale = float(mix["kv"].get("scale", 0.0))
        self.w_uk = jax.random.normal(
            jax.random.fold_in(self.root, W_TAG), (self.h, self.dn, self.r),
            jnp.float32) / np.sqrt(self.dn)

    def _rows(self, uids, starts, t: int):
        return rows_split(self.root, np.asarray(uids, np.int32),
                          np.asarray(starts, np.int32), t=t,
                          width=self.r + self.dr, dist=self.dist,
                          scale=self.scale)

    def feed_each(self, uids, starts, t: int) -> list:
        return [(rows,) for rows in self._rows(uids, starts, t)]

    def q(self, uids, positions):
        return queries(self.root, np.asarray(uids, np.int32),
                       np.asarray(positions, np.int32), h=self.h, dn=self.dn,
                       dr=self.dr)

    def q_each(self, uids, positions):
        return absorbed_split(*self.q(uids, positions), self.w_uk)

    def session_state(self, uid: int, length: int, chunk: int = 512):
        """(rows (length, r + dr) bf16, W_UK)."""
        rows = [self._rows([uid], [c0], chunk)[0][:, 0]
                for c0 in range(0, length, chunk)]
        return jnp.concatenate(rows)[:length], self.w_uk


def reference_answers(ref, q, state, lengths, kv_dtype=None):
    q_nope, q_rope = q
    rows, w_uk = state
    return ref.attend(q_nope, q_rope, rows, w_uk, lengths, kv_dtype=kv_dtype)


@dataclass(frozen=True)
class Geometry:
    """One latent row per token: no separate V page."""

    page: int
    lanes: int
    h: int
    r: int
    dr: int

    @property
    def token_bytes(self) -> int:       # one latent row, bf16
        return (self.r + self.dr) * 2

    def seq_bytes(self, tokens: int, packed_share: float) -> float:
        slot = self.page * self.token_bytes + (self.r + self.dr + 2) * 2
        return work.paged_bytes(tokens, packed_share, page=self.page,
                                lanes=self.lanes, slot_bytes=slot)

    def attend_flops(self, tokens: int) -> int:
        """Scores over r + dr lanes and the weighted sum over r, per
        head."""
        return 2 * self.h * (2 * self.r + self.dr) * tokens


def geometry(config: dict, loop) -> Geometry:
    h, _, r, dr = _widths(config)
    return Geometry(int(config["assumed"]["page"]), loop.cache.group_lanes,
                    h, r, dr)
