"""Dynamic compressed gradient collectives (the paper's §VI applied to DP).

int8 per-tensor quantization with error feedback around an explicit psum
(shard_map path).  THE Dynamic-CRAM saturating counter
(repro.compression.gate) gates the mechanism at runtime: benefit = bytes
saved on the wire, cost = quality signal (relative quantization error) — if
the gradient distribution makes int8 too lossy, compression turns itself
off, exactly like the paper's compression gate.  Lossless CRAM/BDI line
packing is also measured on the gradient bytes (reported by benchmarks;
real bf16 gradients rarely pack, which is itself a finding consistent with
Fig. 4's data-dependence).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..bandwidth.adapters import (
    grad_wire_event,
    int8_wire_bytes,
    tree_wire_bytes,
)
from ..compression.gate import (  # noqa: F401  (COUNTER_MAX re-exported)
    COUNTER_MAX,
    ENABLE_THRESHOLD,
    counter_step,
    wire_counter_step,
)

ENABLE = ENABLE_THRESHOLD  # legacy alias


def quantize_int8(g):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compress_tree(grads, err):
    """Error-feedback int8 compression of a gradient tree.

    Returns (dequantized grads, new error feedback, rel_err scalar).
    """
    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, s = quantize_int8(g32)
        dq = dequantize(q, s)
        return dq.astype(g.dtype), g32 - dq

    flat = jax.tree.map(one, grads, err)
    dq = jax.tree.map(lambda t: t[0], flat,
                      is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda t: t[1], flat,
                           is_leaf=lambda x: isinstance(x, tuple))
    num = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)))
              for a, b in zip(jax.tree.leaves(dq), jax.tree.leaves(grads),
                  strict=True))
    den = sum(jnp.sum(jnp.square(b.astype(jnp.float32)))
              for b in jax.tree.leaves(grads))
    rel_err = jnp.sqrt(num / jnp.maximum(den, 1e-30))
    return dq, new_err, rel_err


def gate_update(counter, rel_err, *, err_budget: float = 0.05,
                bytes_saving: float = 0.75):
    """Saturating-counter gate: wire-bytes saved vs quality cost.  The
    scaling constants live in compression.gate (§VI thresholds have one
    home); `bytes_saving` is the measured fractional wire-byte win."""
    return wire_counter_step(counter, bytes_saving, rel_err > err_budget,
                             jnp)


def gate_enabled(counter):
    return counter >= ENABLE_THRESHOLD


def make_dp_compressed_step(model, mesh, *, lr=1e-3,
                            policy: str = "dynamic", ledger=None):
    """Explicit-collective DP train step with gated int8 grad compression.

    shard_map over the 'data' axis: per-shard grads -> (optionally
    quantized) psum -> AdamW-style SGD update.  Used by tests and the
    grad-compression benchmark; the pjit path keeps XLA-inserted
    collectives.

    policy: "dynamic" (the §VI gate; "auto" is an alias — the AutoTuner's
    runtime decision rule IS the gate), "static" (always quantize), "off"
    (plain collectives).  A bandwidth `ledger` books each step's wire
    bytes (raw vs what the gate actually sent) under consumer "grad".
    """
    from jax.sharding import PartitionSpec as P

    assert policy in ("dynamic", "static", "off", "auto")
    dynamic = policy in ("dynamic", "auto")

    def step(params, err, counter, batch):
        def shard_fn(params, err, counter, batch):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)

            def reduce_plain(g):
                return jax.tree.map(
                    lambda x: jax.lax.pmean(x, "data"), g)

            def reduce_q(g, e):
                dq, new_e, rel = compress_tree(g, e)
                summed = jax.tree.map(
                    lambda x: jax.lax.pmean(x, "data"), dq)
                return summed, new_e, rel

            if dynamic:
                enabled = gate_enabled(counter)
            else:
                enabled = jnp.asarray(policy == "static")
            dq, new_err, rel = reduce_q(grads, err)
            plain = reduce_plain(grads)
            grads_out = jax.tree.map(
                lambda a, b: jnp.where(enabled, a, b), dq, plain)
            new_err = jax.tree.map(
                lambda e, z: jnp.where(enabled, e, z * 0.0),
                new_err, new_err)
            # measured wire-byte win of the int8 collective for THIS tree
            # (adapters own the byte math), fed to the §VI counter
            saving = 1.0 - int8_wire_bytes(grads) / tree_wire_bytes(grads)
            counter_new = (gate_update(counter, rel, bytes_saving=saving)
                           if dynamic else counter)
            new_params = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32)
                              - lr * g.astype(jnp.float32)).astype(p.dtype),
                params, grads_out)
            return new_params, new_err, counter_new, jax.lax.pmean(
                loss, "data")

        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P("data")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(params, err, counter, batch)

    jit_step = jax.jit(step)
    if ledger is None:
        return jit_step

    def step_with_ledger(params, err, counter, batch):
        # the counter entering the step is what gated this step's wire
        enabled = (bool(np.asarray(counter) >= ENABLE_THRESHOLD)
                   if dynamic else policy == "static")
        out = jit_step(params, err, counter, batch)
        grad_wire_event(ledger, params, enabled=enabled)
        return out

    return step_with_ledger
