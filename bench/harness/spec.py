"""Find everything that belongs to one cell by the names in
BENCHMARK.json: its configuration, attention kind, traffic mix,
correctness limits, reference, and the reader of each of its metrics.

A cell's files are looked up under `bench/`: `kinds/<kind>.py`,
`traffic/<mix>.json`, `checks/<cell>.json` and `configs/<reference>.py`.
A BENCHMARK.json given from elsewhere (tests) has the same subdirectories
beside it searched first."""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
DEFAULT_KIND = "gqa"      # a configuration that names no "kind"


def _module(path: Path, name: str):
    """The module at `path`, loaded once per process under `name`
    (registered, so that its dataclasses resolve their annotations)."""
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _find(dirs: tuple, sub: str, filename: str) -> Path:
    for d in dirs:
        path = d / sub / filename
        if path.exists():
            return path
    raise FileNotFoundError(f"{sub}/{filename} under none of "
                            f"{[str(d) for d in dirs]}")


def kind(name: str, dirs: tuple = (BENCH_DIR,)):
    """The attention kind's module: `kinds/<name>.py`."""
    return _module(_find(dirs, "kinds", f"{name}.py"), "bench_kind_" + name)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    checks: dict
    end_to_end: list          # metric entries reported with --trace 0
    per_layer: list           # metric entries reported with --trace 1
    dirs: tuple = (BENCH_DIR,)

    def reference(self):
        name = self.config["reference"]
        return _module(_find(self.dirs, "configs", f"{name}.py"),
                       "bench_reference_" + name)

    def kind(self):
        return kind(self.config.get("kind", DEFAULT_KIND), self.dirs)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(cell: str, benchmark: Path | None = None) -> Cell:
    path = Path(benchmark) if benchmark else ROOT / "BENCHMARK.json"
    dirs = (path.parent, BENCH_DIR) if benchmark else (BENCH_DIR,)
    bm = json.loads(path.read_text())
    wl = {w["name"]: w for w in bm["workloads"]}
    if cell not in wl:
        raise KeyError(f"unknown workload {cell!r}; have {sorted(wl)}")
    w = wl[cell]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads(_find(dirs, "traffic", f"{w['traffic']}.json")
                     .read_text())
    checks = json.loads(_find(dirs, "checks", f"{cell}.json").read_text())
    return Cell(cell, int(w["chips"]), config, mix, checks,
                [m for m in bm["end_to_end"] if _applies(m, cell)],
                [m for m in bm["per_layer"] if _applies(m, cell)], dirs)


def reader(metric: str):
    """The reader module of one metric: `bench/metrics/<metric>.py`."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    return _module(path, "bench_metric_" + metric.replace(".", "_"))
