"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 1 - (union of operation intervals) / window."""


def read(r):
    if r.trace is None or not r.trace.window_s or not r.trace.chips:
        return None
    return 100.0 * (1.0 - r.trace.busy_s() / r.trace.window_s)
