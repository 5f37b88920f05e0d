"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

The layer stack is split into `n_stages` contiguous stages; stage s's
parameters live only on the devices of mesh axis 'stage' index s.  A
shard_map loop runs M microbatches through the classic GPipe schedule:
T = M + P - 1 ticks, activations hopping stage->stage+1 by collective
permute each tick.  Backward is obtained by jax.grad through the loop
(ppermute is linear, so AD produces the reverse schedule automatically —
a hand-scheduled 1F1B would overlap better; noted as future §Perf work).

Multi-pod use: the 'pod' axis of the production mesh can serve as the
stage axis (2 stages across 2 pods), putting the low-bandwidth inter-pod
links on the once-per-tick activation hop instead of every collective.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L//n_stages, ...)."""
    def re(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])

    return jax.tree.map(re, stacked_params)


def gpipe_apply(stage_params, x_mb, *, mesh: Mesh, stage_fn,
                axis: str = "stage"):
    """Run microbatches through the pipeline.

    stage_params: leaves (n_stages, layers_per_stage, ...), sharded on axis.
    x_mb: (M, mb, S, D) microbatched activations, replicated.
    stage_fn(params_local, x) applies one stage's layers.
    Returns (M, mb, S, D) outputs of the final stage (replicated).
    """
    n_stages = mesh.shape[axis]
    M = x_mb.shape[0]
    T = M + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params_local, x_all):
        params_local = jax.tree.map(lambda t: t[0], params_local)
        stage = jax.lax.axis_index(axis)
        cur = jnp.zeros_like(x_all[0])
        outs = jnp.zeros_like(x_all)
        for t in range(T):
            recv = jax.lax.ppermute(cur, axis, perm)
            mb_idx = min(t, M - 1)
            inp = jnp.where(stage == 0, x_all[mb_idx], recv)
            active = (t >= stage) & (t - stage < M)
            out = stage_fn(params_local, inp)
            cur = jnp.where(active, out, jnp.zeros_like(out))
            out_idx = t - (n_stages - 1)
            is_last = stage == n_stages - 1
            write = is_last & (out_idx >= 0)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(write, cur, outs[max(out_idx, 0)]),
                max(out_idx, 0), 0)
        # surface the last stage's outputs everywhere
        last = jnp.where(stage == n_stages - 1, 1.0, 0.0).astype(outs.dtype)
        return jax.lax.psum(outs * last, axis)

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(), check_vma=False,
    )(stage_params, x_mb)
