"""idle_share: the share of the traced window in which no operation ran on
the device, in percent: 1 minus the union of the device's op intervals
over the window."""

from bench import devtrace


def read(run):
    tr = run.trace
    if tr is None or not tr.devices():
        return None
    busy, win = devtrace.busy_s(tr), devtrace.window_s(tr)
    return 100.0 * (1.0 - busy / win) if win else None
