"""Opt-in kernel performance counters at the dispatch boundary.

When a :class:`Profiler` is installed (``GEMMINI_PROFILE=1`` env,
``serve --profile``, or an explicit :func:`install`),
``ExecutionContext.__getattr__`` wraps every op dispatch: the call is
timed with a blocking ``jax.block_until_ready`` sync and recorded into a
per-(op, shape-signature) bucket, joined with the op's
`KernelContract`-derived FLOPs/bytes (:mod:`repro.obs.kernel_costs`).
Dividing by the published peaks of the chip that ran the ops
(`analysis/roofline.PEAKS`, keyed by ``device_kind``) gives achieved
compute/memory utilization per kernel instantiation — the software
analog of the paper's hardware counters. A device without published
peaks (the CPU) gets timings and no utilization.

Profiling applies only to EAGER dispatches (the same trace-state rule
the fault injector follows): a timer inside a
jit trace would measure tracing, not execution, and the blocking sync
would serialize the compiled pipeline.  Ops dispatched inside a jitted
engine step are invisible here — profile with an eager/interpret
context (the tests and ``bench_kernels`` do), or read whole-step timing
from the engine's trace spans instead.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.analysis import roofline
from repro.obs import kernel_costs

ENV_VAR = "GEMMINI_PROFILE"


def _shape_sig(args: Tuple, kw: Dict[str, Any]) -> str:
    parts: List[str] = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            dtype = getattr(a, "dtype", "")
            parts.append(f"{tuple(shape)}{dtype}")
        elif a is None:
            parts.append("-")
        else:
            parts.append(repr(a))
    for k in sorted(kw):
        v = kw[k]
        if getattr(v, "shape", None) is not None:
            v = f"{tuple(v.shape)}{v.dtype}"
        parts.append(f"{k}={v}")
    return ",".join(parts)


@dataclasses.dataclass
class OpBucket:
    """Aggregated timings for one (op, shape-signature) instantiation."""

    op: str
    sig: str
    contract: Optional[str] = None
    flops: float = 0.0            # per call
    bytes: float = 0.0            # per call
    arith: str = "float"
    calls: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0

    def record(self, dt_s: float) -> None:
        self.calls += 1
        self.total_s += dt_s
        self.min_s = min(self.min_s, dt_s)
        self.max_s = max(self.max_s, dt_s)

    def utilization(self, peaks: Optional[roofline.ChipPeaks]
                    ) -> Dict[str, Optional[float]]:
        """Achieved-vs-roofline fractions from the bucket's BEST call
        (min_s): warmup/compile noise inflates means, and the roofline
        question is what the kernel can sustain. ``peaks`` is the chip the
        timings came from; None (a device with no published peaks, such as
        the CPU) leaves every fraction unmeasured."""
        none = {"compute": None, "memory": None, "bound": None}
        if peaks is None or not self.calls or self.min_s == float("inf"):
            return none
        if self.flops <= 0 and self.bytes <= 0:
            return none
        peak = peaks.ops_int8 if self.arith == "int" else peaks.flops_bf16
        cu = (self.flops / self.min_s) / peak
        mu = (self.bytes / self.min_s) / peaks.hbm_bw
        t_c = self.flops / peak
        t_m = self.bytes / peaks.hbm_bw
        return {"compute": cu, "memory": mu,
                "bound": "compute" if t_c >= t_m else "memory"}

    def row(self, peaks: Optional[roofline.ChipPeaks]) -> Dict[str, Any]:
        util = self.utilization(peaks)
        return {
            "op": self.op, "sig": self.sig, "contract": self.contract,
            "calls": self.calls, "total_s": self.total_s,
            "min_s": None if self.min_s == float("inf") else self.min_s,
            "max_s": self.max_s, "flops": self.flops, "bytes": self.bytes,
            "arith": self.arith, "compute_util": util["compute"],
            "memory_util": util["memory"], "bound": util["bound"],
        }


class Profiler:
    """Per-op timing + contract-cost aggregation.

    ``tracer``: optional :class:`repro.obs.trace.Tracer`; when set, each
    profiled call also lands as a ``cat="kernel"`` complete span on the
    profile track.
    """

    def __init__(self, *, clock=time.perf_counter, tracer=None) -> None:
        import jax
        self.clock = clock
        self.tracer = tracer
        self.buckets: Dict[Tuple[str, str], OpBucket] = {}
        self.device_kind = jax.devices()[0].device_kind
        # Utilization divides by the peaks of the chip that ran the ops; a
        # device with no published peaks reports none.
        self.peaks = roofline.PEAKS.get(self.device_kind)

    def bucket(self, op: str, args: Tuple, kw: Dict[str, Any], cfg
               ) -> OpBucket:
        sig = _shape_sig(args, kw)
        key = (op, sig)
        b = self.buckets.get(key)
        if b is None:
            b = self.buckets[key] = OpBucket(op=op, sig=sig)
            cost = kernel_costs.op_cost(op, args, kw, cfg)
            if cost is not None:
                b.contract = cost.contract
                b.flops = cost.flops
                b.bytes = cost.bytes
                b.arith = cost.arith
        return b

    def record(self, bucket: OpBucket, t0: float, t1: float) -> None:
        bucket.record(t1 - t0)
        if self.tracer is not None:
            from repro.obs import trace as otrace
            self.tracer.complete(
                bucket.op, t0, t1, cat="kernel", tid=otrace.TID_PROFILE,
                contract=bucket.contract, flops=bucket.flops,
                bytes=bucket.bytes, sig=bucket.sig,
                device_kind=self.device_kind)

    # -------------------------------------------------------------- report

    def table(self, *, by: str = "total_s") -> List[Dict[str, Any]]:
        rows = [b.row(self.peaks) for b in self.buckets.values()]
        rows.sort(key=lambda r: r.get(by) or 0.0, reverse=True)
        return rows

    def report(self, *, top: int = 20) -> str:
        rows = self.table()[:top]
        if not rows:
            return "profiler: no ops recorded"
        head = (f"{'op':<24} {'contract':<24} {'calls':>6} {'total_ms':>9} "
                f"{'best_ms':>8} {'gflops':>8} {'comp%':>6} {'mem%':>6} "
                f"{'bound':>8}")
        lines = [head, "-" * len(head)]
        for r in rows:
            cu = r["compute_util"]
            mu = r["memory_util"]
            lines.append(
                f"{r['op']:<24} {str(r['contract']):<24} {r['calls']:>6} "
                f"{r['total_s'] * 1e3:>9.3f} "
                f"{(r['min_s'] or 0.0) * 1e3:>8.3f} "
                f"{r['flops'] / 1e9:>8.2f} "
                f"{'--' if cu is None else format(cu * 100, '.2f'):>6} "
                f"{'--' if mu is None else format(mu * 100, '.2f'):>6} "
                f"{str(r['bound'] or '--'):>8}")
        return "\n".join(lines)

    def snapshot(self) -> List[Dict[str, Any]]:
        return self.table()


# ------------------------------------------------------ global installation

_ACTIVE: Optional[Profiler] = None


def install(profiler: Optional[Profiler] = None) -> Profiler:
    global _ACTIVE
    _ACTIVE = profiler or Profiler()
    return _ACTIVE


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[Profiler]:
    global _ACTIVE
    if _ACTIVE is None:
        spec = os.environ.get(ENV_VAR, "").strip().lower()
        if spec not in ("", "0", "off", "false", "no"):
            _ACTIVE = Profiler()
    return _ACTIVE
