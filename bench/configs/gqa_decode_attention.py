"""Plain reference: one decode-attention step over one attention layer's
KV, grouped-query (GQA), in float32 at the highest matmul precision.

Query head h attends KV head h // (Hq // n_kv), as in the configuration's
published attention.  No cache, no paging, no compression, no kernel:
what the served layer has to compute, from the KV it was fed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("kv_dtype",))
def attend(q, k, v, lengths, *, kv_dtype=None):
    """q (P, Hq, d) float32; k, v (L, n_kv, d); lengths (P,) int32.
    Answer p attends positions [0, lengths[p]).  `kv_dtype` rounds K and
    V through a lower precision first (the control).  Returns
    (P, Hq, d) float32."""
    if kv_dtype is not None:
        k, v = k.astype(kv_dtype), v.astype(kv_dtype)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    p, hq, d = q.shape
    n_kv = k.shape[1]
    qg = q.reshape(p, n_kv, hq // n_kv, d)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("pkgd,lkd->pkgl", qg, k, precision=hi) / jnp.sqrt(
        jnp.float32(d))
    live = jnp.arange(k.shape[0])[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("pkgl,lkd->pkgd", w, v, precision=hi)
    return o.reshape(p, hq, d)
