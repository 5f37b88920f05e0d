"""The benchmark harness: one general runner for every cell.

A cell (an entry of BENCHMARK.json's `workloads`) names a configuration
(`bench/configs/<config>.json`), a traffic mix (`bench/traffic/<mix>.json`,
read by the one generator in `traffic.py`) and, through the metric lists,
per-metric readers (`bench/metrics/<metric>.py`).  The configuration
names its attention kind (`"kind"`, GQA where absent), and
`bench/kinds/<kind>.py` holds all the harness knows of it: the serve
loop it builds, the payload and queries it feeds, the call into the
plain reference, and the work it counts.  The harness finds all of them
by name, so a new cell, mix, metric or kind is a new file.

Modules:
  spec     — BENCHMARK.json, configuration, traffic and reader lookup
  device   — the chip check, the peaks table, the allocator's peak
  kvgen    — on-device value and query streams, keyed by (seed, session,
             position), that the kinds' generators shape
  traffic  — the session plan generator (sizes, turns, arrivals)
  warmup   — drives every program shape the window can reach, in set-up
  runner   — set-up, the timed window, and what it records
  check    — the comparison with the plain reference that decides
             `correct`
  trace    — profiler trace -> events -> busy, idle, program time
  work     — bytes and operations the CRAM layout must move and compute,
             from the kind's work object
  stats    — percentiles and spreads
  cell     — one run of one cell, end to end, and its result line
"""
