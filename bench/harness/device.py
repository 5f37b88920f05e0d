"""The chip check, the peaks table, and the allocator's peak."""

from __future__ import annotations

import json
from pathlib import Path


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def describe() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check(chips: int) -> dict:
    """The device as JAX reports it; NoChip unless it is a TPU with at
    least `chips` chips.  There is no CPU fallback."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise NoChip(f"no TPU: jax found {dev}")
    if dev["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips; jax found {dev}")
    return dev


def peaks(bench_dir: Path, kind: str) -> dict:
    """The per-chip peaks of `kind`; an unknown device is an error."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return dict(table["devices"][kind], source=table["source"])


def memory_peak_bytes(n_devices: int) -> int:
    """Peak bytes in use on the fullest of the first `n_devices`
    devices (0 where the backend keeps no statistics)."""
    import jax

    peak = 0
    for d in jax.devices()[:n_devices]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak
