"""kernel_roofline.decode, the decode kernel's own share of its roofline,
and the existing trace readers beside the program's `serve.*` spans: on
a hand-made trace whose answers are known, and on the trace recorded on
a TPU v5e (fixtures/trace_v5e.json)."""

from pathlib import Path

import pytest

from harness import spec, trace, work
from harness.cell import Run
from harness.runner import Record

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6   # ns
FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e.json"
CTX = [24576, 24576, 25600, 25600, 26624, 26624, 27648, 27648]

# the trace readers of the accepted cells, read from the same events
EXISTING = ("megastep_ms", "attend_roofline.decode", "step_mfu",
            "device_idle_share.decode", "host_ms_per_step.decode")


def _run(events, attends):
    geo = spec.kind("gqa").Geometry(16, 2, 8, 128, 24)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    rec = Record(t0=0.0, t_end=1.0, tokens=8, steps=1, attends=attends,
                 appended=8 * len(attends))
    return Run(cell="x", geo=geo, peaks=peaks, rec=rec, setup_s=1.0,
               memory_peak_bytes=1, counts={},
               packed={"live_groups": 200, "packed_live_groups": 150},
               trace=trace.Trace(events))


def _hand_made():
    # window 0..100 ms; one attend program of 40 ms holding the view copy
    # (10 ms) and the kernel (25 ms), a second kernel op cut by the window
    return [
        (HOST, "python3", "bench.window", 0.0, 100 * MS),
        (HOST, "python3", "bench.step", 0.0, 20 * MS),
        (HOST, "python3", "bench.attend", 20 * MS, 10 * MS),
        (DEV, "XLA Modules", "jit__megastep(3)", 5 * MS, 10 * MS),
        (DEV, "XLA Modules", "jit_decode_attention_fused(9)", 30 * MS,
         40 * MS),
        (DEV, "XLA Ops", "fusion.7", 5 * MS, 10 * MS),
        (DEV, "XLA Ops", "copy.4", 30 * MS, 10 * MS),
        (DEV, "XLA Ops", "cram_decode_attention_batched.1", 40 * MS,
         25 * MS),
        # another op's name, as a chip trace writes it: the whole HLO
        # instruction
        (DEV, "XLA Ops", "%cram_decode_attention_batched_copy.1 = s16[8] "
         "copy(s16[8] %p)", 65 * MS, 1 * MS),
        (DEV, "XLA Ops", "%cram_decode_attention_batched.2 = (f32[8,3], "
         "s32[8,2]) custom-call(f32[8,3] %copy-done.1), "
         'custom_call_target="tpu_custom_call"', 95 * MS,
         10 * MS),                                 # 5 ms in the window
    ]


def _with_program_spans(events):
    """`events` plus `serve.*` spans nested inside each `bench.step` and
    `bench.attend`, as ServeLoop writes them, and a span on a second
    thread."""
    out = list(events)
    for plane, line, name, a, d in events:
        if name == "bench.step":
            out += [(plane, line, "serve.step", a + 0.1 * d, 0.8 * d),
                    (plane, line, "serve.megastep", a + 0.3 * d, 0.5 * d),
                    (plane, line, "serve.megastep.dispatch", a + 0.6 * d,
                     0.1 * d)]
        elif name == "bench.attend":
            out += [(plane, line, "serve.attend", a + 0.1 * d, 0.8 * d),
                    (plane, line, "serve.attend.q", a + 0.2 * d, 0.1 * d),
                    (plane, line, "serve.attend.prep", a + 0.4 * d,
                     0.2 * d),
                    (plane, line, "serve.attend.dispatch", a + 0.7 * d,
                     0.1 * d)]
    w = next(e for e in events if e[2] == trace.WINDOW_SPAN)
    out.append((HOST, "spill-worker", "serve.evict", w[3], w[4]))
    return out


def _read(name, run):
    got = spec.reader(name).read(run)
    return got[0] if isinstance(got, tuple) else got


def test_kernel_time_is_the_named_ops_alone():
    kr = spec.reader("kernel_roofline.decode")
    t = trace.Trace(_hand_made())
    assert kr.kernel_s(t) == pytest.approx(0.030)     # 25 + 5 ms
    assert t.program_s([r"^jit_decode_attention_fused"]) == pytest.approx(
        0.040)


def test_kernel_roofline_known_answer():
    attends = [(0.0, 0.0, CTX)]
    run = _run(_hand_made(), attends)
    geo, peaks = run.geo, run.peaks
    least, _ = work.bound(*work.attend_work(CTX, 0.75, geo), peaks)
    val, more = spec.reader("kernel_roofline.decode").read(run)
    assert val == pytest.approx(100.0 * least / 0.030)
    assert more == {"bound": "hbm"}
    # the same work over the whole attend program reads lower
    assert _read("attend_roofline.decode", run) == pytest.approx(
        100.0 * least / 0.040)


def test_kernel_roofline_is_silent_without_the_kernel():
    events = [e for e in _hand_made()
              if "cram_decode_attention_batched." not in e[2]]
    assert spec.reader("kernel_roofline.decode").read(
        _run(events, [(0.0, 0.0, CTX)])) is None
    run = _run(_hand_made(), [])
    run.trace = None
    assert spec.reader("kernel_roofline.decode").read(run) is None


@pytest.mark.parametrize("source", ["hand_made", "recorded"])
def test_existing_readers_ignore_program_spans(source):
    if source == "recorded":
        if not FIXTURE.exists():
            pytest.skip("no recorded trace")
        events = trace.read(str(FIXTURE))
        attends = [(0.0, 0.0, CTX)] * 3
    else:
        events, attends = _hand_made(), [(0.0, 0.0, CTX)]
    spanned = _with_program_spans(events)
    assert len(spanned) > len(events)
    for name in EXISTING:
        got = _read(name, _run(spanned, attends))
        assert got is not None, name
        assert got == _read(name, _run(events, attends)), name
    assert (trace.Trace(spanned).idle_gaps()
            == trace.Trace(events).idle_gaps())


def test_recorded_kernel_share():
    """On the v5e trace the kernel is most of the attend program, and its
    share of the roofline reads above the program's and below 100%."""
    if not FIXTURE.exists():
        pytest.skip("no recorded trace")
    events = trace.read(str(FIXTURE))
    kr = spec.reader("kernel_roofline.decode")
    t = trace.Trace(events)
    ops = [e for e in events if e[1] == trace.OP_LINE
           and e[2].startswith("cram_decode_attention_batched.")]
    assert ops
    assert kr.kernel_s(t) == pytest.approx(sum(e[4] for e in ops) * 1e-9,
                                           rel=1e-9)
    prog = t.program_s([r"^jit_decode_attention_fused"])
    assert 0.5 * prog < kr.kernel_s(t) < prog
    run = _run(events, [(0.0, 0.0, CTX)] * len(ops))
    k, a = _read("kernel_roofline.decode", run), _read(
        "attend_roofline.decode", run)
    assert a < k < 100.0
