"""device_idle_share: 1 - (union of the device's busy intervals) over
the traced window, in %."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()
