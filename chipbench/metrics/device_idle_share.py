"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window."""


def read(v):
    if v.trace is None or v.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - v.trace.busy_s / v.trace.window_s)
