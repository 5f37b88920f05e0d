"""A configuration can bring its own attention kind as new files.

`fixtures/seam/` holds a BENCHMARK.json with one cell whose
configuration names a kind the harness does not know (`latent_test`: one
latent row per token, not a (k, v) pair; queries in two parts; a
reference that needs a seeded weight; answers of the latent's width),
with its kind module, reference, traffic mix and checks.  The cell runs
end to end, is correct, its control is not, and the program's faults
show (`test_cells`, `test_control`, `test_faults` take it as a case).
Here: its work object reaches the readers, and the harness names
neither it nor any GQA width on the run path."""

from pathlib import Path

import pytest
from conftest import SEAM_BENCHMARK, SEAM_CELL

from harness import spec, trace, work
from harness.cell import Run
from harness.runner import Record

CELL = SEAM_CELL


def test_latent_work_reaches_the_readers():
    """The kind's work object stands in for the GQA geometry: the step's
    share of the peak reads from it on the recorded v5e trace."""
    cell = spec.load(CELL, SEAM_BENCHMARK)
    geo = cell.kind().Geometry(page=16, lanes=2, h=128, r=512, dr=64)
    assert geo.token_bytes == 1152
    assert geo.attend_flops(1) == 128 * (576 + 512) * 2
    b, f = work.attend_work([1000], 0.0, geo)
    assert b == 63 * (16 * 1152 + 578 * 2) and f == 1000 * 278528
    events = trace.read(str(Path(__file__).parent / "fixtures"
                            / "trace_v5e.json"))
    rec = Record(t0=0.0, t_end=1.0, tokens=8, steps=1,
                 attends=[(0.0, 0.0, [4096] * 8)], appended=8)
    # 278,528 operations a token: about 227 per byte read raw, under the
    # v5e's ridge of 241, and twice that packed, over it
    for packed, bound in ((0, "hbm"), (10, "flops")):
        run = Run(cell=CELL, geo=geo, peaks={"hbm_bytes_per_s": 819e9,
                                             "bf16_flops_per_s": 197e12},
                  rec=rec, setup_s=1.0, memory_peak_bytes=1, counts={},
                  packed={"live_groups": 10, "packed_live_groups": packed},
                  trace=trace.Trace(events))
        val, more = spec.reader("step_mfu").read(run)
        assert 0 < val < 100 and more == {"bound": bound}


def test_the_harness_does_not_know_the_kind():
    for path in (spec.BENCH_DIR / "harness").glob("*.py"):
        text = path.read_text()
        for word in ("latent", "kv_lora_rank", "qk_rope"):
            assert word not in text, (path.name, word)


@pytest.mark.parametrize("name", ["cell", "runner", "check", "work"])
def test_the_run_path_names_no_gqa_width(name):
    text = (spec.BENCH_DIR / "harness" / f"{name}.py").read_text()
    for word in ("n_kv", "head_dim", "num_key_value_heads", "kv_each",
                 "session_kv"):
        assert word not in text, word
