"""device_idle: share of the traced window in which no operation ran on
the device: 1 - (union of the device's operation intervals) / window."""


def read(r, peaks):
    if r.trace is None or not r.trace.devices:
        return None
    return 100.0 * r.trace.idle_share()
