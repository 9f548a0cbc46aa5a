"""A kernel's share of its roofline over a traced window."""

from __future__ import annotations

from typing import Optional

from bench import devtrace


def bound_s(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of the operations
    over the bf16 peak and the bytes over the HBM bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])


def roofline_share(run, kernel: str, pattern: str) -> Optional[float]:
    """Percent: the summed least time of every call of ``kernel`` that the
    traced steps made (``step_costs(...)[kernel]``: one [operations,
    bytes] per call or group of identical calls), over the summed device
    time of the ops matching ``pattern`` inside those steps' executions.
    None where the steps and the trace cannot be paired, or the trace
    holds no such op."""
    tr = run.trace
    if tr is None or not tr.devices() or run.peaks is None:
        return None
    dev = tr.devices()[0]
    steps = run.traced_dispatches
    pairs = devtrace.step_modules(tr, dev, [e["which"] for e in steps])
    if not pairs:
        return None
    t = devtrace.op_time_ns(tr, dev, pattern, [m for _, m in pairs]) / 1e9
    if t <= 0:
        return None
    cfg = run.cell.config
    least = sum(bound_s(f, b, run.peaks)
                for e in steps[len(steps) - len(pairs):]
                for f, b in run.cell.model.step_costs(cfg, e).get(kernel, []))
    return 100.0 * least / t if least > 0 else None
