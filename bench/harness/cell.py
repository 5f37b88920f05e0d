"""One run of one cell: set-up, the timed window, the trace, the check,
and the result line."""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

from . import check, device, spec, trace, work
from .runner import Runner, Sampler
from .kvgen import Generator
from .stats import percentile
from .traffic import make_plan
from .warmup import Warmer

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
    "/jax/core/compile/backend_compile_duration": "compiled",
}


class CompileCounter:
    """Programs traced and compiled by this process (one listener)."""

    def __init__(self):
        import jax

        self.n = {"lowered": 0, "compiled": 0}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.n[COMPILE_EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


@dataclass
class Run:
    """What the metric readers read."""

    cell: str
    geo: work.Geometry
    peaks: dict
    rec: object
    setup_s: float
    memory_peak_bytes: int
    counts: dict                   # ServeLoop.counts deltas over the window
    packed: dict                   # live / packed groups after the window
    trace: trace.Trace | None = None

    @property
    def window_s(self) -> float:
        return self.rec.t_end - self.rec.t0

    def packed_share(self) -> float:
        p = self.packed
        return p["packed_live_groups"] / max(1, p["live_groups"])


def process_start() -> float:
    """Wall-clock time this process started."""
    import psutil

    return psutil.Process().create_time()


def _packed(loop) -> dict:
    import numpy as np

    c = loop.cache
    mask = np.asarray(c.state["packed_mask"])
    live = packed = 0
    for slot in range(c.batch):
        g = c.slot_groups(slot)
        live += g
        packed += int(mask[slot, :g].sum())
    return {"live_groups": live, "packed_live_groups": packed}


def _tokens_mismatch(runner) -> int:
    """Resident sessions whose slot holds another token count than the
    harness appended (the cache's own per-slot counter): an append the
    program dropped or doubled.  Compressible KV hides such a token from
    the attend output, whose values barely differ between tokens."""
    loop = runner.loop
    bad = 0
    for s in runner.live.values():
        rec = loop.seqs.get(s.uid)
        if rec is None or rec.spilled:
            continue
        bad += int(loop.cache.tokens_b[rec.slot]) != s.tokens
    return bad


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell_name: str, *, seed: int, seconds: float, trace_on: bool,
        require_tpu: bool = True, control: bool = False,
        overrides: dict | None = None, peaks_kind: str | None = None,
        counter: CompileCounter | None = None,
        keep_trace: str | None = None,
        benchmark: Path | None = None) -> dict:
    """One run; returns the result object (the last stdout line).
    `overrides` (tests only) patch the configuration and the mix:
    {"config": {...}, "mix": {...}, "checks": {...}}; `benchmark` (tests
    only) reads the cell from another BENCHMARK.json."""
    t_start = process_start()
    cell = spec.load(cell_name, benchmark)
    if overrides:
        for part in ("config", "mix", "checks"):
            getattr(cell, part).update(overrides.get(part, {}))
    dev = device.check(cell.chips) if require_tpu else device.describe()
    peaks = device.peaks(spec.BENCH_DIR, peaks_kind or dev["kind"])
    _log(f"device: platform {dev['platform']}, device_kind "
         f"{dev['kind']!r}, count {dev['count']}")

    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving import ServeLoop

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = counter or CompileCounter()

    cfg, mix = cell.config, cell.mix
    page = int(cfg["assumed"]["page"])
    n_kv, hq = int(cfg["num_key_value_heads"]), int(cfg["num_attention_heads"])
    d = int(cfg["assumed"]["head_dim"])
    plan = make_plan(mix, seed)
    # the serve tier at its defaults, or as the mix's "serve" entry sets
    # it (e.g. {"packing": "quad"})
    loop = ServeLoop(slots=plan.slots, max_pages=plan.capacity // page,
                     page=page, n_kv=n_kv, head_dim=d, **mix.get("serve", {}))
    if require_tpu:
        assert loop.cache.interpret is False, "Pallas kernels interpreted"
    geo = work.Geometry(page, loop.cache.group_lanes, n_kv, d, hq)
    gen = Generator(seed, n_kv=n_kv, d=d, hq=hq, kv=mix["kv"])
    smp = cell.checks["sample"]
    runner = Runner(loop, gen, plan, sampler=Sampler(
        seed, smp["answers"], smp.get("woken", 0)))
    warmer = Warmer(runner)
    t = time.time()
    warmer.run()
    t_warm = time.time() - t
    t = time.time()
    runner.admit_all()
    t_admit = time.time() - t
    t = time.time()
    if plan.endless:
        warmer.run_endless()
    t_warm += time.time() - t
    loop.spill.flush()
    counts0 = dict(loop.counts)
    before = counter.snapshot()
    setup_s = time.time() - t_start
    _log(f"setup: {setup_s:.3f} s to the first timed step (warm-up "
         f"{t_warm:.3f} s, admission {t_admit:.3f} s, {before['compiled']} "
         f"programs compiled, cache {cache_dir})")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace_on else None
    if trace_on:
        jax.profiler.start_trace(tdir)
    rec = runner.window(seconds)
    jax.block_until_ready(loop.cache.state)
    loop.spill.flush()
    if trace_on:
        jax.profiler.stop_trace()
    after = counter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    peak = device.memory_peak_bytes(cell.chips)
    counts = {k: loop.counts[k] - counts0[k] for k in loop.counts}
    packed = _packed(loop)
    _log(f"window: {rec.t_end - rec.t0:.3f} s, {rec.tokens} tokens, "
         f"{rec.steps} steps, {len(rec.turns_due)} turns due "
         f"({rec.censored} censored), programs lowered in window "
         f"{in_window['lowered']}, compiled {in_window['compiled']}; "
         f"counts {counts}; packed {packed}")
    tr = None
    if trace_on:
        events = trace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        if keep_trace:
            trace.save(events, keep_trace)
        tr = trace.Trace(events)

    tokens_mismatch = _tokens_mismatch(runner)
    # free the program's state before the reference runs
    items = runner.sampler.items()
    runner.loop = warmer.loop = loop = None
    gc.collect()
    t = time.time()
    readings = check.compare(items, gen, cell.reference(),
                             control=cfg["assumed"]["kv_dtype"]
                             if control else None)
    readings["tokens_mismatch"] = tokens_mismatch
    _log(f"reference: {time.time() - t:.3f} s for "
         f"{readings['answers_checked']} answers")
    ok, shown = check.judge(readings, cell.checks["limits"])

    r = Run(cell.name, geo, peaks, rec, setup_s, peak, counts, packed, tr)
    metrics = {}
    for m in (cell.per_layer if trace_on else cell.end_to_end):
        got = spec.reader(m["name"]).read(r)
        if got is None:
            continue
        val, more = got if isinstance(got, tuple) else (got, {})
        metrics[m["name"]] = {"value": val, "unit": m["unit"], **more}
    dev_out = dict(dev, memory_peak_bytes=peak)
    lim = cell.checks["limits"].get("attend_rel_err", {}).get("max",
                                                              math.inf)
    attempted = rec.tokens + rec.censored
    failed = rec.censored + sum(e > lim for e in readings.pop("errs"))
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev_out}
    if tr is not None:
        dev_out["busy_s"] = tr.busy_s()
        dev_out["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_programs(),
                               "idle_gaps": tr.idle_gaps()}
    result["window"] = {"compiles_in_window": in_window,
                        "lateness_p95_s": percentile(rec.lateness, 95),
                        "control_rel_err": readings.get("control_rel_err")}
    result["checks"] = shown
    for name, s in shown.items():
        _log(f"check {name} {s['value']} {s['holds']} limit {s['limit']}")
    return result
