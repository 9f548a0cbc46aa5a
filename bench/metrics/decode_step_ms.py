"""decode_step_ms: device time of one decode step, in milliseconds: the
summed durations of the decode step's executions in the device trace over
their count."""

from bench import devtrace


def read(run):
    tr = run.trace
    if tr is None or not tr.devices():
        return None
    pairs = devtrace.step_modules(tr, tr.devices()[0],
                                  [e["which"] for e in run.traced_dispatches])
    ns = [m.dur_ns for w, m in pairs or [] if w == "decode"]
    return sum(ns) / len(ns) / 1e6 if ns else None
