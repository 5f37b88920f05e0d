"""itl_p95_ms: 95th percentile of every gap between successive tokens of
one answer in the window, spilled time included (host clock)."""

from harness.stats import percentile


def read(run):
    v = percentile(run.rec.itl, 95)
    return None if v is None else v * 1e3
