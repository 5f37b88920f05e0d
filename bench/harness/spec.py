"""Find everything that belongs to one cell by the names in
BENCHMARK.json: its configuration, traffic mix, correctness limits,
reference, and the reader of each of its metrics."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    checks: dict
    end_to_end: list          # metric entries reported with --trace 0
    per_layer: list           # metric entries reported with --trace 1

    def reference(self):
        return _module(BENCH_DIR / "configs" / f"{self.config['reference']}.py",
                       "bench_reference_" + self.config["reference"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(cell: str, benchmark: Path | None = None) -> Cell:
    bm = json.loads((benchmark or ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bm["workloads"]}
    if cell not in wl:
        raise KeyError(f"unknown workload {cell!r}; have {sorted(wl)}")
    w = wl[cell]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    checks = json.loads((BENCH_DIR / "checks" / f"{cell}.json").read_text())
    return Cell(cell, int(w["chips"]), config, mix, checks,
                [m for m in bm["end_to_end"] if _applies(m, cell)],
                [m for m in bm["per_layer"] if _applies(m, cell)])


def reader(metric: str):
    """The reader module of one metric: `bench/metrics/<metric>.py`."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    return _module(path, "bench_metric_" + metric.replace(".", "_"))
