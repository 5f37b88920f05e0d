"""Pallas TPU kernels for the CRAM compute hot-spots (+ pure-jnp oracles).

  compress_scan.py  one-pass image compressibility + marker classification
                    (device backend of the line codecs in
                    repro.compression.codecs)
  bdi_pack.py       CRAM-KV 2:1 pair / 4:1 quad packing and unpacking
                    (device backends of the int8-delta / int4-delta codecs)
  cram_attention.py fused marker-check/unpack/flash-decode attention
  prefill_pack.py   bulk pack of a whole prompt's page groups
  ops.py            public jit'd wrappers over the KV kernels
  ref.py            pure-jnp oracles (the allclose/equality targets;
                    thin jnp bindings of repro.compression.pagepack)

Every kernel takes `interpret=None`, resolved by `resolve_interpret`: the
kernels compile through Mosaic on a TPU and run in Pallas interpret mode
only where the default backend is the CPU (the test suite).
`tests/test_tpu_compile.py` compiles them for a described v5e chip, and
`chip_smoke.py` runs them on one.  repro.compression's numpy paths stay
the bit-true source of truth.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place `interpret=None` is decided: interpret on the CPU
    backend, compiled everywhere else (a non-TPU accelerator then fails
    loudly instead of silently interpreting)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
