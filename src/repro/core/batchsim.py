"""Batched multi-workload × multi-scheme sweep — the engine's vmapped side.

The step function lives in `core.engine` (shared verbatim with the scalar
simulator in memsim.py); this module owns the batched dispatch:

  * **scheme axis** — engine (flags, params) rows stacked to (S, N_FLAGS)
    / (S, N_PARAMS) and vmapped.  Because params are traced data, config
    ablations (LCT size, sampling threshold, counter init — see
    schemes.variant) ride in the same dispatch as behaviour variants:
    a Fig. 14-style LCT-size sensitivity sweep is just more rows.
  * **workload axis** — traces stacked to (W, T) and vmapped; optionally
    sharded across devices with `shard_map` (clean single-device
    fallback when only one device is present or W doesn't divide).
  * **time axis** — `chunk_size` splits the scan into a Python loop of
    jitted chunk dispatches with a donated carry (bounded compile/live
    memory for very long traces; donation is a no-op on CPU).

Exactness contract: all execution modes produce bit-identical int32 stats
to the scalar path — lax.scan is sequential whether run whole or chunked,
and sharding only partitions the already-independent workload axis.
tests/test_batchsim.py and tests/test_engine.py assert this exactly.

Entry points:
  sweep(...)            — raw (S, W, N_STATS) stats from stacked traces
  sweep_workloads(...)  — build traces for named workloads, run one batched
                          dispatch, return {name: run_workload-style dict}
"""

from __future__ import annotations

import functools

import numpy as np

from . import schemes as schemes_registry
from .engine import N_STATS, SimConfig, build_engine  # noqa: F401
from .memsim import SCHEMES, summarize_stats, summarize_workload


def scheme_flags(schemes) -> np.ndarray:
    """(S, N_FLAGS) int32 flag matrix (back-compat: schemes.flags_matrix)."""
    return schemes_registry.flags_matrix(schemes)


def _vmapped(run):
    """vmap over workloads (axis after flags/params), then over schemes."""
    import jax

    run_w = jax.vmap(run, in_axes=(None, None, 0, 0, 0, 0, 0))
    return jax.vmap(run_w, in_axes=(0, 0, None, None, None, None, None))


@functools.lru_cache(maxsize=None)
def _jit_sweep(cfg: SimConfig):
    import jax

    return jax.jit(_vmapped(build_engine(cfg).run_one))


@functools.lru_cache(maxsize=None)
def _jit_sweep_sharded(cfg: SimConfig, n_dev: int):
    """The same batched program with the workload axis sharded over
    `n_dev` devices via shard_map (no collectives: workloads are
    independent, each device runs the full scheme axis on its shard)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("w",))
    # check_vma off: the engine's scan carry starts replicated and turns
    # workload-varying, which the varying-axis check rejects
    fn = jax.shard_map(
        _vmapped(build_engine(cfg).run_one), mesh=mesh,
        in_specs=(P(), P(), P("w"), P("w"), P("w"), P("w"), P("w")),
        out_specs=P(None, "w"), check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _jit_sweep_chunked(cfg: SimConfig):
    """(init, chunk) pair for the chunked batched path.  The chunk carry is
    donated so long sweeps reuse the state buffers in place (no-op on CPU,
    where XLA does not implement donation)."""
    import jax

    eng = build_engine(cfg)
    chunk_w = jax.vmap(eng.run_chunk, in_axes=(0, None, None, 0, 0, 0, 0, 0))
    chunk_sw = jax.vmap(chunk_w,
                        in_axes=(0, 0, 0, None, None, None, None, None))
    donate = () if jax.default_backend() == "cpu" else (0,)
    init_s = jax.jit(jax.vmap(eng.init_state))
    return init_s, jax.jit(chunk_sw, donate_argnums=donate)


def _resolve_axis(schemes, cfg):
    import jax.numpy as jnp

    resolved = [schemes_registry.resolve(s) for s in schemes]
    return (resolved,
            jnp.asarray(schemes_registry.flags_matrix(resolved)),
            jnp.asarray(schemes_registry.params_matrix(resolved, cfg)))


def sweep(schemes, addrs, is_write, pair_ab, pair_cd, quad,
          cfg: SimConfig = SimConfig(), *, chunk_size: int | None = None,
          shard: "bool | str" = "auto") -> np.ndarray:
    """Run every scheme × workload pair in one batched dispatch.

    schemes: registry names and/or schemes.Scheme records (the scheme AND
    config axis — variants with different params batch together).
    addrs/is_write: (W, T); pair_ab/pair_cd/quad: (W, n_groups) bool.
    chunk_size: optional time-chunked execution (Python loop of jitted
    chunk dispatches with a donated carry).  Chunked execution is
    single-device; combining it with shard=True raises.
    shard: "auto" shards the workload axis over all local devices when
    there are several and W divides evenly; True forces it (still falling
    back cleanly when impossible); False keeps a single-device dispatch.

    Returns int32 stats of shape (len(schemes), W, N_STATS), laid out per
    the engine's ST_* indices — bit-identical across execution modes.
    """
    import jax
    import jax.numpy as jnp

    _, flags, params = _resolve_axis(schemes, cfg)
    a = jnp.asarray(addrs, jnp.int32)
    w = jnp.asarray(is_write)
    tail = (jnp.asarray(pair_ab), jnp.asarray(pair_cd), jnp.asarray(quad))

    if chunk_size:
        if shard is True:
            raise ValueError(
                "chunk_size and shard=True cannot be combined; chunked "
                "execution runs the workload axis on one device")
        init_s, chunk = _jit_sweep_chunked(cfg)
        per_scheme = init_s(params)
        n_w = a.shape[0]
        carry = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[:, None], (x.shape[0], n_w) + x.shape[1:]),
            per_scheme)
        for lo in range(0, a.shape[1], chunk_size):
            hi = lo + chunk_size
            carry = chunk(carry, flags, params, a[:, lo:hi], w[:, lo:hi],
                          *tail)
        return np.asarray(carry[-1])

    n_dev = len(jax.devices())
    want_shard = shard is True or (shard == "auto" and n_dev > 1)
    if want_shard and n_dev > 1 and a.shape[0] % n_dev == 0:
        fn = _jit_sweep_sharded(cfg, n_dev)
    else:
        fn = _jit_sweep(cfg)
    return np.asarray(fn(flags, params, a, w, *tail))


def sweep_workloads(names=None, schemes=SCHEMES, n_events: int = 200_000,
                    seed: int = 0, cfg: SimConfig = SimConfig(), *,
                    chunk_size: int | None = None,
                    shard: "bool | str" = "auto") -> dict:
    """Batched replacement for {name: memsim.run_workload(name)} loops.

    Builds the named traces (identical generators/seeds to the scalar path),
    stacks them, and runs a single batched dispatch covering all schemes and
    workloads.  Returns {name: summary} where each summary is field-for-field
    identical to memsim.run_workload's output.
    """
    from .traces import all_workload_names, build_workload

    names = list(names) if names is not None else all_workload_names()
    requested = [schemes_registry.resolve(s) for s in schemes]
    req_names = [s.name for s in requested]
    # a baseline run is required for speedup normalization
    sim_schemes = (requested if "baseline" in req_names
                   else [schemes_registry.get("baseline"), *requested])

    metas, fs = [], []
    addrs, wrs, pabs, pcds, pqs = [], [], [], [], []
    for name in names:
        meta, a, w, pab, pcd, pq, f = build_workload(name, n_events, seed)
        metas.append(meta)
        fs.append(f)
        addrs.append(a)
        wrs.append(w)
        pabs.append(pab)
        pcds.append(pcd)
        pqs.append(pq)

    stats = sweep(
        sim_schemes,
        np.stack(addrs), np.stack(wrs),
        np.stack(pabs), np.stack(pcds), np.stack(pqs),
        cfg, chunk_size=chunk_size, shard=shard,
    )

    out = {}
    sim_names = [s.name for s in sim_schemes]
    base_row = sim_names.index("baseline")
    for wi, name in enumerate(names):
        results = {
            sch: summarize_stats(sch, stats[si, wi])
            for si, sch in enumerate(sim_names) if sch in req_names
        }
        base = summarize_stats("baseline", stats[base_row, wi]).accesses
        out[name] = summarize_workload(name, fs[wi], results, base)
    return out
