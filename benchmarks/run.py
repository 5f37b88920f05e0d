"""Benchmark driver: batched sweep CLI + legacy per-module tables.

Sweep mode (the fast path — ONE batched jitted dispatch per section):

    python benchmarks/run.py --sweep all            # memsim + compress + serve
                                                    #   + codecs + policy
    python benchmarks/run.py --sweep memsim         # Fig. 12/15/16/18, Table V
    python benchmarks/run.py --sweep compress       # Pallas image scan (Fig. 4)
    python benchmarks/run.py --sweep serve          # CRAM-KV decode curves
    python benchmarks/run.py --sweep codecs         # codec x layout registry
                                                    #   table
    python benchmarks/run.py --sweep policy         # AutoTuner chosen-vs-best-
                                                    #   static (no-slowdown)
    python benchmarks/run.py --sweep serve-spill    # continuous-batching churn
                                                    #   + compressed KV spill
    python benchmarks/run.py --sweep kernels        # batched fused-decode
                                                    #   BlockSpec tuning sweep

Sweep flags:
    --events N        trace length per workload   (default $REPRO_BENCH_EVENTS
                      or 300000)
    --workloads a,b   comma-separated workload subset (default: full suite)
    --schemes x,y     comma-separated scheme subset   (default: the six paper
                      schemes + registry extras: cram-nollp, cram@lct64/128/256)
    --serve-steps N   decode steps per serve curve (default 32)
    --serve-batches a,b  serve-curve batch sizes (default 1,4)
    --out PATH        report path (default experiments/sweep_report.json)
    --force           ignore the on-disk suite cache

The consolidated JSON report written by --sweep has this schema:

    {
      "config":   {"sweep"; plus "n_events", "schemes", "workloads"
                   when a memsim sweep ran — compress ignores those flags},
      "memsim":   {                     # present for --sweep memsim/all
        "n_events", "sweep_wall_s",
        "speedups":        {workload: {scheme: speedup}},
        "fig12_by_suite":  {suite: {scheme: geomean speedup}},
        "fig16_geomean":   {scheme: geomean speedup},
        "fig18_worst":     {scheme: min speedup},
        "fig18_best":      {scheme: max speedup},
        "fig8_explicit_bandwidth":  {workload: normalized breakdown},
        "fig15_cram_bandwidth":     {workload: normalized breakdown},
        "table5_prefetch_pct":      {"<suite>_<scheme>": percent},
        "llp_value":       {cram / cram-nollp geomeans + llp_gain_pct},
        "lct_sensitivity": {lct_size: {geomean_speedup,
                            mean_one_access_rate}}  # cram@lct* config axis
        "workloads":       {workload: full memsim.run_workload summary}
      },
      "compress": {                     # present for --sweep compress/all
        "per_source": {source: {"pair_fits_64B", "pair_fits_60B",
                                 "mean_size", "status_counts"}},
        "overall":    {...same keys...},
        "lines_scanned", "wall_s"
      },
      "serve": {                        # present for --sweep serve/all
        "curves":    [per (packing x policy x batch x compressibility)
                      decode curve: seq_len / pack_pairs_per_step / bytes
                      per step / fit_rate / pages_per_slot...],
        "quad":      {curve: {int4_fit_rate, pages_per_slot, saving}},
        "pack_work": {"mean_pack_pairs_per_step", "mean_total_pairs",
                      "full_rebuild_work_ratio"},   # incremental-repack win
        "static_compressible_saving",
        "parity":    {"incremental_equals_rebuild", "kernel_vs_oracle_err"}
      },
      "codecs": {                       # present for --sweep codecs/all
        "line64":   {"per_workload": {workload: {codec: {mean_size, ratio,
                      group4 packing stats}}},
                     "size_mlines_per_s": {codec: throughput}},
        "kv_pages": {stream: {page_codec: {fit_rate, layout,
                      pages_per_slot}}},
        "tensors":  {tensor: {codec: ratio}}       # ckpt/gradient bytes
      },
      "serve_spill": {                  # present for --sweep serve-spill/all
        "backend":     {platform, device_kind},   # throughput rows are
                                                  #   backend-scoped
        "curves":      {spill_packing: churn curve — spill/ledger/decode
                        summaries, wall_s, wake_state_parity},
        "incompressible_quad": same curve on a noise stream,
        "spill_bytes": {spill_packing: {raw, stored, saving}},
        "migration":   {"gate"/"repack": live-migration churn curve —
                        per-phase tokens/s (steady / migrating /
                        spill_churn), no_stall, bit_identical},
        "prefill":     {fused / replay wall+tokens_per_s, speedup,
                        bit_identical},   # ONE-dispatch bulk-pack ingest
                                          #   vs token-by-token replay
        "guarantee":   {same_schedule_across_packings,
                        compressed_moves_fewer_bytes, spill_no_slowdown,
                        wake_state_parity, migration_no_stall,
                        migration_bit_identical,
                        prefill_no_slower_than_replay}  # CI-enforced
      },
      "kernels": {                      # present for --sweep kernels/all
        "modes": {"lanes2"/"lanes4": {"rows": [per block_groups tiling:
                   us_per_call, max_err_vs_oracle, numerics_parity,
                   bytes_bit_exact], "best_block_groups", "saving_on_mix"}},
        "parity_ok": bool               # CI fails when False
      },
      "policy": {                       # present for --sweep policy/all
        "kv":         {stream: {chosen, bytes: {off/pair/quad/auto},
                       best_static, regret_vs_best,
                       auto_not_worse_than_off}},
        "checkpoint": {tensor: {chosen, stored: {codec: bytes, auto},
                       best_static, auto_not_worse_than_off}},
        "grad":       {profile: {chosen, rel_err, wire_bytes,
                       auto_not_worse_than_off}},
        "guarantee":  bool              # auto never worse than static-off
      }
    }

Legacy mode (unchanged CSV): `python benchmarks/run.py [module ...]` runs
the per-figure modules and prints ``name,us_per_call,derived`` rows,
mirroring everything to experiments/bench_results.json.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

MODULES = [
    "codec_sweep",
    "policy_sweep",
    "fig4_compressibility",
    "fig12_speedup",
    "fig14_llp",
    "fig15_bandwidth",
    "table3_storage",
    "table4_channels",
    "table5_prefetch",
    "kernel_bench",
    "serve_bench",
    "dryrun_summary",
    "roofline_report",
]


def _sweep_memsim(args) -> dict:
    from benchmarks.memsim_suite import DEFAULT_SCHEMES, suite_results
    from benchmarks.sweep_report import build_report

    # default: six paper schemes + registry extras (cram-nollp ablation and
    # the cram@lct* config axis) — all rows of ONE batched dispatch
    schemes = tuple(args.schemes.split(",")) if args.schemes else DEFAULT_SCHEMES
    workloads = args.workloads.split(",") if args.workloads else None
    suite = suite_results(force=args.force, n_events=args.events,
                          workloads=workloads, schemes=schemes)
    return build_report(suite)


def _sweep_compress(args) -> dict:
    """One-pass Pallas compressibility scan over the Fig. 4 corpus."""
    import numpy as np

    from benchmarks.fig4_compressibility import _corpus, pair_fit_stats
    from repro.kernels.compress_scan import compress_scan

    t0 = time.time()
    corpus = _corpus()
    names, images = zip(*sorted(corpus.items()), strict=True)
    lines = np.concatenate([v.reshape(-1, 64) for v in images])
    out = compress_scan(lines)          # single kernel dispatch, whole image

    def stats(sizes, status):
        p64, p60 = pair_fit_stats(sizes)
        uniq, cnt = np.unique(status, return_counts=True)
        return {
            "pair_fits_64B": p64,
            "pair_fits_60B": p60,
            "mean_size": float(sizes.mean()),
            "status_counts": {int(u): int(c) for u, c in zip(uniq, cnt, strict=True)},
        }

    per_source, ofs = {}, 0
    for name, img in zip(names, images, strict=True):
        n = img.size // 64
        per_source[name] = stats(out["sizes"][ofs:ofs + n],
                                 out["status"][ofs:ofs + n])
        ofs += n
    return {
        "per_source": per_source,
        "overall": stats(out["sizes"], out["status"]),
        "lines_scanned": int(lines.shape[0]),
        "wall_s": round(time.time() - t0, 2),
    }


def _sweep_serve(args) -> dict:
    """CRAM-KV decode-bandwidth/packing curves (incremental batched cache)."""
    from benchmarks.serve_bench import sweep

    batches = tuple(int(b) for b in args.serve_batches.split(","))
    return sweep(batches=batches, decode_steps=args.serve_steps)


def _sweep_codecs(args) -> dict:
    """Per-codec x per-layout registry table (workload line distributions,
    KV page streams, checkpoint/gradient tensors)."""
    from benchmarks.codec_sweep import sweep

    workloads = args.workloads.split(",") if args.workloads else None
    return sweep(workloads=workloads)


def _sweep_policy(args) -> dict:
    """AutoTuner chosen-vs-best-static audit (the no-slowdown guarantee)."""
    from benchmarks.policy_sweep import sweep

    return sweep(decode_steps=args.serve_steps)


def _sweep_kernels(args) -> dict:
    """BlockSpec tuning sweep for the batched fused decode kernel, with
    parity columns CI fails on (BENCH_kernels.json snapshot)."""
    from benchmarks.kernel_bench import blockspec_sweep

    return blockspec_sweep()


def _sweep_serve_spill(args) -> dict:
    """Continuous-batching churn with compressed KV spill: same schedule
    under spill packing off/pair/quad + the no-slowdown guarantee flags."""
    from benchmarks.serve_bench import spill_sweep

    return spill_sweep(steps=args.serve_steps)


def run_sweep(args) -> None:
    # --events/--workloads/--schemes only shape the memsim section; the
    # compress scan always covers the fixed Fig. 4 corpus, so record the
    # flags under "config" only when a memsim sweep ran with them.
    report: dict = {"config": {"sweep": args.sweep}}
    if args.sweep in ("memsim", "all"):
        report["config"].update(
            n_events=args.events,
            schemes=args.schemes or "all",
            workloads=args.workloads or "all",
        )
        report["memsim"] = _sweep_memsim(args)
        g = report["memsim"]["fig16_geomean"]
        print("memsim geomean speedups:",
              " ".join(f"{s}={v:.4f}" for s, v in g.items()))
        print("table5:", {k: round(v, 1) for k, v in
                          report["memsim"]["table5_prefetch_pct"].items()})
        lct = report["memsim"]["lct_sensitivity"]
        if lct:
            print("lct sensitivity:",
                  " ".join(f"{n}={d['geomean_speedup']:.4f}"
                           for n, d in lct.items()))
        llp = report["memsim"]["llp_value"]
        if "llp_gain_pct" in llp:
            print(f"llp value: +{llp['llp_gain_pct']:.2f}% geomean "
                  "(cram vs cram-nollp)")
    if args.sweep in ("compress", "all"):
        report["compress"] = _sweep_compress(args)
        o = report["compress"]["overall"]
        print(f"compress scan: {report['compress']['lines_scanned']} lines, "
              f"p64={o['pair_fits_64B']:.3f} p60={o['pair_fits_60B']:.3f}")
    if args.sweep in ("codecs", "all"):
        report["codecs"] = _sweep_codecs(args)
        thr = report["codecs"]["line64"]["size_mlines_per_s"]
        kv = report["codecs"]["kv_pages"]
        print("codec sweep:",
              " ".join(f"{c}={v:.2f}Ml/s" for c, v in thr.items()))
        print("kv pack rates:",
              {s: {c: round(d["fit_rate"], 2) for c, d in row.items()}
               for s, row in kv.items()})
    if args.sweep in ("serve", "all"):
        report["serve"] = _sweep_serve(args)
        pw = report["serve"]["pack_work"]
        pr = report["serve"]["parity"]
        print(f"serve: pack/step={pw['mean_pack_pairs_per_step']:.2f} pairs "
              f"(full rebuild would be {pw['mean_total_pairs']:.1f}), "
              f"static saving={report['serve']['static_compressible_saving']:.3f}, "
              f"incr==rebuild={pr['incremental_equals_rebuild']}")
        q = report["serve"]["quad"]
        if q:
            print("serve quad:",
                  {k: f"pps={d['pages_per_slot']:.2f}"
                      f"/fit={d['int4_fit_rate']:.2f}"
                   for k, d in q.items()})
    if args.sweep in ("serve-spill", "all"):
        report["serve_spill"] = _sweep_serve_spill(args)
        sb = report["serve_spill"]["spill_bytes"]
        print("serve-spill savings:",
              " ".join(f"{spk}={d['saving']:.4f}" for spk, d in sb.items()))
        mig = report["serve_spill"]["migration"]
        print("serve-migration:",
              " ".join(f"{mode}={m['migrating_over_steady']:.2f}x"
                       f"(pend={m['pending_columns_at_flip']})"
                       for mode, m in mig.items()))
        pf = report["serve_spill"]["prefill"]
        print(f"serve-prefill: T={pf['prompt_tokens']} "
              f"fused={pf['fused']['tokens_per_s']:.0f} tok/s "
              f"replay={pf['replay']['tokens_per_s']:.0f} tok/s "
              f"({pf['speedup']:.1f}x, "
              f"bit_identical={pf['bit_identical']})")
        flags = report["serve_spill"]["guarantee"]
        print("serve-spill guarantee:", flags)
        if not all(flags.values()):
            print("SERVE-SPILL GUARANTEE VIOLATED", file=sys.stderr)
    if args.sweep in ("kernels", "all"):
        report["kernels"] = _sweep_kernels(args)
        for mode, m in report["kernels"]["modes"].items():
            print(f"kernels {mode}: best block_groups="
                  f"{m['best_block_groups']} "
                  f"saving={m['saving_on_mix']:.3f} "
                  + " ".join(f"bg{r['block_groups']}={r['us_per_call']:.0f}us"
                             for r in m["rows"]))
        if not report["kernels"]["parity_ok"]:
            print("KERNEL PARITY VIOLATED", file=sys.stderr)
    if args.sweep in ("policy", "all"):
        report["policy"] = _sweep_policy(args)
        pol = report["policy"]
        chosen = {s: {n: r["chosen"] for n, r in pol[s].items()}
                  for s in ("kv", "checkpoint", "grad")}
        print("policy chosen:", chosen)
        print("policy guarantee (auto never worse than off): "
              f"{pol['guarantee']}")
        if not pol["guarantee"]:
            print("POLICY GUARANTEE VIOLATED", file=sys.stderr)
    out_path = Path(args.out) if args.out else (
        _ROOT / "experiments" / "sweep_report.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(report, indent=1))
    print(f"report -> {out_path}")


def run_legacy(only) -> list:
    """Run the per-figure modules; returns the names of those that raised
    (each still reports an ERROR row and the suite keeps going)."""
    all_rows, failed = [], []
    print("name,us_per_call,derived")
    for mod_name in MODULES:
        if only and mod_name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run()
        except Exception as e:  # keep the suite running
            traceback.print_exc()
            failed.append(mod_name)
            rows = [(f"{mod_name}/ERROR", 0.0, repr(e)[:100])]
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            all_rows.append({"name": name, "us_per_call": us,
                             "derived": str(derived)})
        print(f"# {mod_name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    out = _ROOT / "experiments"
    out.mkdir(exist_ok=True)
    (out / "bench_results.json").write_text(json.dumps(all_rows, indent=1))
    return failed


def main() -> None:
    import os

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("modules", nargs="*", choices=MODULES, metavar="MODULE",
                    help="legacy mode: per-figure modules to run "
                         f"({', '.join(MODULES)})")
    ap.add_argument("--sweep",
                    choices=("all", "memsim", "compress", "serve", "codecs",
                             "policy", "serve-spill", "kernels"),
                    help="batched sweep mode; emits one JSON report")
    ap.add_argument("--serve-steps", type=int, default=32,
                    help="decode steps per serve-bench curve")
    ap.add_argument("--serve-batches", default="1,4",
                    help="comma-separated serve-bench batch sizes")
    ap.add_argument("--events", type=int, default=None,
                    help="trace length per workload (sweep mode only; "
                         "legacy mode reads $REPRO_BENCH_EVENTS)")
    ap.add_argument("--workloads", help="comma-separated workload names")
    ap.add_argument("--schemes", help="comma-separated scheme names")
    ap.add_argument("--out", help="sweep report output path")
    ap.add_argument("--force", action="store_true",
                    help="ignore the on-disk suite cache")
    ap.add_argument("--analyze", action="store_true",
                    help="run the repo-invariant static analyzer + jaxpr "
                         "hot-path audit (DESIGN.md §11) before anything "
                         "else; non-zero exit on violations or golden "
                         "drift")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.analyze:
        from repro.analysis.__main__ import main as analysis_main

        rc = analysis_main(["--jaxpr"])
        if rc:
            raise SystemExit(rc)
        if not args.sweep and not args.modules:
            return
    if args.sweep:
        if args.events is None:
            args.events = int(os.environ.get("REPRO_BENCH_EVENTS", 300_000))
        run_sweep(args)
    else:
        given = [f for f, v in (("--events", args.events),
                                ("--workloads", args.workloads),
                                ("--schemes", args.schemes),
                                ("--out", args.out),
                                ("--force", args.force or None)) if v]
        if given:
            ap.error(f"{', '.join(given)} require(s) --sweep; legacy mode "
                     "is configured via $REPRO_BENCH_EVENTS")
        failed = run_legacy(args.modules or None)
        if failed:
            raise SystemExit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
