"""Every cell runs end to end at tiny size on the CPU (Pallas interpret
mode): the control flow, the comparison with the reference, and the keys
of the result line; so does a cell of an attention kind the harness does
not know (`conftest.SEAM_CELL`).  Also: the command refuses to run
without a TPU."""

import json
import os
import subprocess
import sys

import pytest
from conftest import CHAT_CELL, SEAM_CELL, benchmark_of, run_tiny

from harness import spec

BENCH_CELLS = [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]]
CELLS = BENCH_CELLS + [CHAT_CELL["name"], SEAM_CELL]


def _keys_ok(res, cell, trace_on):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    c = spec.load(cell, benchmark_of(cell))
    want = {m["name"] for m in (c.per_layer if trace_on else c.end_to_end)}
    # on the CPU the allocator keeps no peak; device-trace metrics need a
    # device plane: both are silent there
    silent = {"hbm_peak_gb", "megastep_ms", "attend_roofline.decode",
              "attend_roofline.churn", "step_mfu", "step_mfu.churn",
              "device_idle_share.decode", "device_idle_share.churn"}
    assert want - silent <= set(res["metrics"]) <= want
    for m in res["metrics"].values():
        assert m["value"] == m["value"] and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    for name, s in res["checks"].items():
        assert {"value", "limit"} <= set(s), name


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    res = run_tiny(cell, seconds=2.0)
    _keys_ok(res, cell, False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    # a chat turn due at the window's end may go unanswered (censored)
    assert res["failed"] <= (3 if cell == CHAT_CELL["name"] else 0)
    assert res["window"]["compiles_in_window"]["compiled"] == 0
    if cell.startswith("olmoe"):
        assert res["checks"]["woken_answers_checked"]["value"] >= 1


def test_traced_run_reports_per_layer_metrics():
    cell = "olmoe.chat_sessions.spill"
    res = run_tiny(cell, seconds=2.0, trace_on=True)
    _keys_ok(res, cell, True)
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         BENCH_CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_mix_can_set_the_serve_tier():
    """A new cell is data: a traffic file's "serve" entry reaches
    ServeLoop (here quad packing), and the run stays correct."""
    from conftest import TINY_CONFIG, TINY_MIX

    from harness import cell

    res = cell.run(BENCH_CELLS[0], seed=2**31 + 5, seconds=1.5,
                   trace_on=False, require_tpu=False,
                   peaks_kind="TPU v5 lite",
                   overrides={"config": TINY_CONFIG,
                              "mix": dict(TINY_MIX["endless"],
                                          serve={"packing": "quad"})})
    assert res["correct"], res["checks"]
