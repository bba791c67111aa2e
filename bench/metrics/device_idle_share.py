"""1 − (union of device-busy intervals) ÷ traced window, averaged over
the cell's chips."""


def read(run):
    if run.trace is None or not run.trace.chips_seen:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
