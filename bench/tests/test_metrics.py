"""The metric arithmetic: whole-window rates, tails over every sample
with censored turns included, and the readers' lookup by name."""

import pytest

from harness import spec, stats
from harness.cell import Run
from harness.runner import Record


def _run(rec, **kw):
    geo = spec.kind("gqa").Geometry(16, 2, 8, 128, 24)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    base = dict(cell="x", geo=geo, peaks=peaks, rec=rec, setup_s=12.5,
                memory_peak_bytes=7_000_000_000,
                counts={"evicted": 30, "woken": 28},
                packed={"live_groups": 200, "packed_live_groups": 150})
    base.update(kw)
    return Run(**base)


def test_rate_is_whole_window():
    rec = Record(t0=100.0, t_end=110.0, tokens=2500)
    assert spec.reader("decode_tok_s").read(_run(rec)) == 250.0


def test_tails_over_every_sample():
    rec = Record(t0=0.0, t_end=10.0,
                 itl=[0.010] * 95 + [0.100] * 5)
    assert spec.reader("itl_p95_ms").read(_run(rec)) == pytest.approx(
        stats.percentile([10.0] * 95 + [100.0] * 5, 95))


def test_censored_turns_count_in_the_tail():
    # 90 turns answered in 50 ms; 10 due turns never answered by the end
    # of the window count at their censored times (2 s and more)
    rec = Record(t0=0.0, t_end=30.0,
                 ttft=[0.05] * 90 + [2.0 + i for i in range(10)])
    got = spec.reader("ttft_p90_ms").read(_run(rec))
    assert got == pytest.approx(stats.percentile(
        [50.0] * 90 + [2000.0 + 1000 * i for i in range(10)], 90))
    assert got > 50.0


def test_counters_and_memory():
    rec = Record(t0=0.0, t_end=30.0, turns_due=[1.0] * 29)
    r = _run(rec)
    assert spec.reader("spill_crossings_per_turn").read(r) == 2.0
    assert spec.reader("packed_group_share").read(r) == 75.0
    assert spec.reader("hbm_peak_gb").read(r) == 7.0
    assert spec.reader("setup_s").read(r) == 12.5


def test_trace_metrics_are_silent_without_a_trace():
    r = _run(Record(t0=0.0, t_end=1.0, tokens=1))
    for name in ("megastep_ms", "attend_roofline.decode", "step_mfu",
                 "device_idle_share.decode", "host_ms_per_step.churn"):
        assert spec.reader(name).read(r) is None, name


def test_spread_matches_statistics_quantiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = __import__("statistics").quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / q2)


def test_every_metric_in_the_benchmark_has_a_reader():
    import json

    bm = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert hasattr(spec.reader(m["name"]), "read"), m["name"]
