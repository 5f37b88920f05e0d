"""The reduction from trace events to busy time, idle share, program
time and idle gaps: on a hand-made trace whose answers are known, and on
a small trace recorded on a TPU v5e (tests/fixtures/)."""

from pathlib import Path

import pytest

from harness import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6   # ns


def _hand_made():
    # window 0..100 ms; on the device two programs (ops inside them), one
    # overlapping pair of ops; host spans say what the host was doing
    return [
        (HOST, "python", "bench.window", 0.0, 100 * MS),
        (HOST, "python", "bench.step", 5 * MS, 10 * MS),
        (HOST, "python", "bench.attend", 20 * MS, 40 * MS),
        (HOST, "python", "bench.block", 30 * MS, 30 * MS),
        (HOST, "python", "bench.idle", 70 * MS, 30 * MS),
        (DEV, "XLA Modules", "jit__megastep(12)", 10 * MS, 10 * MS),
        (DEV, "XLA Modules", "jit_decode_attention_fused(40)", 25 * MS,
         30 * MS),
        (DEV, "XLA Ops", "fusion.1", 10 * MS, 10 * MS),
        (DEV, "XLA Ops", "copy.2", 25 * MS, 20 * MS),
        (DEV, "XLA Ops", "custom-call.3", 40 * MS, 15 * MS),
        (DEV, "XLA Ops", "late", 99 * MS, 5 * MS),      # clipped at 100
    ]


def test_hand_made_busy_idle_programs():
    t = trace.Trace(_hand_made())
    assert t.window_s == pytest.approx(0.1)
    assert t.devices == [DEV]
    # ops 10-20, 25-55 (overlap merged), 99-100
    assert t.busy(DEV) == [[10 * MS, 20 * MS], [25 * MS, 55 * MS],
                           [99 * MS, 100 * MS]]
    assert t.busy_s() == pytest.approx(0.041)
    assert t.idle_share() == pytest.approx(0.59)
    assert t.program_s([r"^jit__megastep"]) == pytest.approx(0.010)
    assert t.program_s([r"^jit_decode_attention_fused"]) == pytest.approx(
        0.030)
    assert t.top_programs()[0] == ["jit_decode_attention_fused",
                                   pytest.approx(0.030)]
    assert [d for _, d in t.spans("bench.step")] == [10 * MS]


def test_hand_made_idle_gaps_by_host_span():
    gaps = dict(trace.Trace(_hand_made()).idle_gaps())
    # 0-10 (mid 5: bench.step starts at 5 -> innermost), 20-25 (mid 22.5:
    # attend), 55-99 (mid 77: idle)
    assert gaps == {"host:bench.step": pytest.approx(0.010),
                    "host:bench.attend": pytest.approx(0.005),
                    "host:bench.idle": pytest.approx(0.044)}


def test_union():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e.json"


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace():
    """A short traced window of phi4_mini.longctx.compressible on one TPU
    v5e, cut to a few steps: the reduction agrees with a by-hand sum."""
    ev = trace.read(str(FIXTURE))
    t = trace.Trace(ev)
    assert t.devices and t.devices[0].startswith("/device:TPU")
    ops = [e for e in ev if e[1] == trace.OP_LINE]
    assert ops, "no op events"
    busy = t.busy_s()
    assert 0 < busy <= t.window_s
    # busy is at most the plain sum of op durations (overlaps merged)
    assert busy <= sum(e[4] for e in ops) * 1e-9 + 1e-12
    att = t.program_s([r"^jit_decode_attention_fused"])
    mods = [e for e in ev if e[1] == trace.MODULE_LINE
            and e[2].startswith("jit_decode_attention_fused")]
    assert att == pytest.approx(sum(e[4] for e in mods) * 1e-9, rel=1e-9)
    assert att > 0
    assert len(t.spans("bench.step")) >= 1
    gaps = t.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(
        t.window_s - busy, rel=1e-6)
