"""Benchmark-layer unit tests: fig14 missing-scheme robustness and the
consolidated report's registry-extra sections (no simulation involved —
the suite dict is synthesized)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _summary(schemes, accuracy=0.97):
    from repro.bandwidth.adapters import engine_traffic

    breakdown = {
        "data_reads": 10, "mispredict_extra": 1, "wb_dirty": 2,
        "wb_clean+invalidate": 3, "metadata": 4, "prefetch_extra": 0,
    }
    # the equivalent STAT counters, so the embedded ledger "traffic" view
    # (what bandwidth_breakdowns reads) is consistent with the breakdown
    stats = {"demand_reads": 10, "read_probes": 11, "wb_dirty": 2,
             "wb_clean": 3, "il_writes": 0, "meta_reads": 4, "meta_wb": 0,
             "pf_extra_access": 0}
    return {
        "workload": "x", "": 0.5, "baseline_accesses": 100,
        "schemes": {
            s: {"accesses": 90, "speedup": 1.05, "llp_accuracy": accuracy,
                "meta_hit_rate": 0.5, "breakdown": dict(breakdown),
                "traffic": engine_traffic(stats).as_dict()}
            for s in schemes
        },
    }


def _suite(schemes):
    return {"n_events": 1000, "sweep_wall_s": 0.1,
            "workloads": {"libq": _summary(schemes),
                          "mcf17": _summary(schemes)}}


def test_fig14_skips_missing_schemes(monkeypatch):
    import benchmarks.fig14_llp as fig14

    monkeypatch.setattr(fig14, "suite_results",
                        lambda: _suite(("baseline", "dynamic")))
    rows = fig14.run()  # must not KeyError on the cram/explicit columns
    labels = {r[0]: r[2] for r in rows}
    assert "suite cache lacks: cram,explicit" in labels["fig14/omitted_schemes"]
    assert labels["fig14/mean_llp_accuracy"].startswith("n/a")
    assert labels["fig14/libq"] == "n/a"


def test_fig14_full_suite(monkeypatch):
    import benchmarks.fig14_llp as fig14

    monkeypatch.setattr(fig14, "suite_results",
                        lambda: _suite(("cram", "explicit")))
    rows = fig14.run()
    labels = {r[0]: r[2] for r in rows}
    assert "llp=0.970" in labels["fig14/libq"]
    assert "metaHR=0.500" in labels["fig14/libq"]
    assert not any("omitted" in name for name, _, _ in rows)


def test_build_report_registry_sections():
    from benchmarks.sweep_report import build_report

    suite = _suite(("baseline", "cram", "cram-nollp",
                    "cram@lct64", "cram@lct128"))
    rep = build_report(suite)
    # paper aggregates stay restricted to the six paper schemes
    assert set(rep["fig16_geomean"]) == {"cram"}
    # the extras feed their own sections
    assert set(rep["lct_sensitivity"]) == {"64", "128", "512"}
    assert rep["llp_value"]["llp_gain_pct"] == pytest.approx(0.0)
    assert rep["lct_sensitivity"]["512"]["geomean_speedup"] == \
        pytest.approx(1.05)


def test_fig15_breakdowns_from_ledger_match_legacy_counters():
    """The Fig. 8/15 render path now reads engine_traffic ledger rows
    (engine_breakdown); pin it category-for-category equal to the legacy
    SimResult.bandwidth_breakdown math on a real (small) simulation."""
    from benchmarks.sweep_report import bandwidth_breakdowns
    from repro.core.memsim import run_workload

    summary = run_workload("libq", schemes=("baseline", "cram", "explicit"),
                           n_events=4000, seed=3)
    workloads = {"libq": summary}
    got = bandwidth_breakdowns(workloads)
    base = summary["baseline_accesses"]
    for sch in ("explicit", "cram"):
        b = summary["schemes"][sch]["breakdown"]
        legacy = {
            "data": (b["data_reads"] + b["wb_dirty"]) / base,
            "metadata": b["metadata"] / base,
            "mispredict": b["mispredict_extra"] / base,
            "wbclean+inv": b["wb_clean+invalidate"] / base,
            "total": summary["schemes"][sch]["accesses"] / base,
        }
        assert got[sch]["libq"] == legacy, sch


def test_fig15_rows_render_from_ledger_view(monkeypatch):
    import benchmarks.fig15_bandwidth as fig15

    monkeypatch.setattr(fig15, "suite_results",
                        lambda: _suite(("cram", "explicit")))
    rows = fig15.run()
    labels = {r[0]: r[2] for r in rows}
    # 10 reads + 2 dirty wb over 100 baseline accesses, from ledger rows
    assert labels["fig15/libq"].startswith("data=0.12")
    assert "wbclean+inv=0.03" in labels["fig15/libq"]
    assert "mispred=0.010" in labels["fig8/libq"]


def test_legacy_run_exits_nonzero_when_a_module_raises(monkeypatch):
    """A module that raises still reports its ERROR row and the suite
    keeps going, but the run must not exit 0."""
    import benchmarks.run as br
    import benchmarks.table3_storage as t3

    def boom():
        raise RuntimeError("broken module")

    monkeypatch.setattr(t3, "run", boom)
    monkeypatch.setattr(sys, "argv", ["run.py", "table3_storage"])
    with pytest.raises(SystemExit) as exc:
        br.main()
    assert "table3_storage" in str(exc.value.code)


def test_legacy_run_rejects_unknown_module(monkeypatch):
    import benchmarks.run as br

    monkeypatch.setattr(sys, "argv", ["run.py", "no_such_figure"])
    with pytest.raises(SystemExit) as exc:
        br.main()
    assert exc.value.code == 2
