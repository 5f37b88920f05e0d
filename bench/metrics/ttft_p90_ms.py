"""ttft_p90_ms: 90th percentile over every turn due in the window, from
its due time to its first answer token; a turn with no token by the end
of the window counts at its censored time (host clock)."""

from harness.stats import percentile


def read(run):
    v = percentile(run.rec.ttft, 90)
    return None if v is None else v * 1e3
