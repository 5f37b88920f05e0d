"""Plain reference of the latent test kind: one decode-attention step
over a latent cache, in float32 at the highest matmul precision.

Each cached row is [c, k_rope]: a latent c of `kv_lora_rank` values and
a rotary key of `qk_rope_head_dim`, shared by every query head.  Head h
scores a position by q_nope[h] . (W_UK[h] c) + q_rope[h] . k_rope, the
keys made explicitly from the latent (no absorption), over
sqrt(kv_lora_rank + qk_rope_head_dim), and its answer is the softmax-
weighted sum of the latents c.  No cache, no paging, no kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("kv_dtype",))
def attend(q_nope, q_rope, rows, w_uk, lengths, *, kv_dtype=None):
    """q_nope (P, H, dn), q_rope (P, H, dr) float32; rows (L, r + dr);
    w_uk (H, dn, r) float32; lengths (P,) int32.  Answer p attends
    positions [0, lengths[p]).  `kv_dtype` rounds the rows through a
    lower precision first (the control).  Returns (P, H, r) float32."""
    if kv_dtype is not None:
        rows = rows.astype(kv_dtype)
    rows = rows.astype(jnp.float32)
    r = w_uk.shape[-1]
    c, k_rope = rows[:, :r], rows[:, r:]
    hi = jax.lax.Precision.HIGHEST
    k_nope = jnp.einsum("hnr,lr->lhn", w_uk, c, precision=hi)
    s = (jnp.einsum("phn,lhn->phl", q_nope, k_nope, precision=hi)
         + jnp.einsum("phd,ld->phl", q_rope, k_rope, precision=hi))
    s = s / jnp.sqrt(jnp.float32(rows.shape[-1]))
    live = jnp.arange(rows.shape[0])[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("phl,lr->phr", w, c, precision=hi)
