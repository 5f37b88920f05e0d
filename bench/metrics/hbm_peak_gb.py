"""hbm_peak_gb: the device allocator's peak bytes in use after the
window (set-up included), in GB."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
