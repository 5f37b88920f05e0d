"""Sharded decode-attend: shard_map over the KV batch (slot) axis.

The workload-axis pattern `core.batchsim` proved for the trace engine,
applied to serving: sequence slots are independent, so the batched decode
kernel partitions cleanly across local devices with no collectives — each
device walks its shard of (slots, overflow, strips, masks, valid) with
the full marker table replicated.  Falls back to the single-device
dispatch when only one device is present or the slot count doesn't
divide; both paths are bit-identical (tests/test_serving.py runs the
forced-2-device subprocess parity check).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels import ops as kops


def shard_kv_attend(cache, q, *, shard: "bool | str" = "auto",
                    devices=None):
    """One batched decode-attend over `cache` (a CRAMKVCache or
    SlotKVCache), optionally sharded over the slot axis.

    q: (B, Hq, d) one query row per slot.  Returns (B, Hq, d) float32.
    No bandwidth accounting here — callers charge the step explicitly
    (ServeLoop.attend / account_step)."""
    with TraceAnnotation("serve.attend.prep"):
        cache.repack()
        q = jnp.asarray(q)
        if q.ndim == 2:
            q = q[None]
        lanes = cache.group_lanes
        n = cache._active_bucket()
        kc = cache._kernel_cache(n)
        valid = jnp.asarray(cache.valid_per_page()[:, : lanes * n])
    decode = (kops.decode_attention_batched if cache.packing == "pair"
              else kops.decode_attention_quad_batched)
    devs = list(devices if devices is not None else jax.devices())
    n_dev = len(devs)
    b = q.shape[0]
    want = shard is True or (shard == "auto" and n_dev > 1)
    with TraceAnnotation("serve.attend.dispatch"):
        if not want or n_dev <= 1 or b % n_dev:
            return decode(q, kc, valid, interpret=cache.interpret)
        return _sharded(decode, devs, q, kc, valid, cache.interpret)


def _sharded(decode, devs, q, kc, valid, interpret):
    """`decode` under shard_map over the slot axis of `devs`."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(devs), ("kv",))
    markers = kc["markers"]      # replicated (closed over, shared table)

    def one_shard(qq, slots, over, strips, mask, vv):
        c = {"slots": slots, "slots_overflow": over, "strips": strips,
             "packed_mask": mask, "markers": markers}
        return decode(qq, c, vv, interpret=interpret)

    fn = jax.shard_map(one_shard, mesh=mesh,
                       in_specs=(P("kv"),) * 6, out_specs=P("kv"),
                       check_vma=False)  # pallas_call has no varying-axis
    # rule; every spec is explicit so nothing is inferred
    return fn(q, kc["slots"], kc["slots_overflow"], kc["strips"],
              kc["packed_mask"], valid)


__all__ = ["shard_kv_attend"]
