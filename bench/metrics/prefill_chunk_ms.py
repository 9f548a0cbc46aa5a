"""prefill_chunk_ms: device time of one prefill step (first chunks and
continuation chunks, with or without logits), in milliseconds: the summed
durations of their executions in the device trace over their count."""

from bench import devtrace

PREFILL = ("prefill", "prefill_nl", "chunk", "chunk_nl")


def read(run):
    tr = run.trace
    if tr is None or not tr.devices():
        return None
    pairs = devtrace.step_modules(tr, tr.devices()[0],
                                  [e["which"] for e in run.traced_dispatches])
    ns = [m.dur_ns for w, m in pairs or [] if w in PREFILL]
    return sum(ns) / len(ns) / 1e6 if ns else None
