#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, correctness limits and metric
readers are found by the names in BENCHMARK.json (see bench/harness).
Set-up (warm-up of every program shape the window reaches, then the
admission of the cell's sessions) is timed as `setup_s`; the window then
drives `repro.serving.ServeLoop` for `--seconds`; the program's state is
freed and a plain reference checks a seeded sample of the window's
answers.  `--trace 1` profiles the window and reports the per-layer
metrics instead of the end-to-end ones.  `--control 1` also reads the
control (the reference in a lower precision), for setting limits.

Exits 2 with no result line when JAX finds no TPU, or fewer chips than
the cell asks for.  The compared numbers and their limits are the last
lines on standard error and the last key of the result.
"""

from __future__ import annotations

import argparse
import json
import sys

from harness import cell, device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: also save the trace's events here")
    args = ap.parse_args(argv)
    try:
        result = cell.run(args.workload, seed=args.seed, seconds=args.seconds,
                          trace_on=bool(args.trace),
                          control=bool(args.control),
                          keep_trace=args.keep_trace)
    except device.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
