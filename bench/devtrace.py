"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes an ``.xplane.pb``. Its device planes
(``/device:TPU:<n>``) hold a line of XLA ops and a line of XLA modules, one
module event per execution of a compiled program; the host planes hold the
benchmark's own ``jax.profiler.TraceAnnotation`` spans, on the same clock:

* ``bench:window`` -- the traced span of the measured window;
* ``bench:scheduler`` -- one ``engine.step()``;
* ``step:<which>`` -- the enqueue of one jitted model step;
* ``sampling`` -- the host waiting for logits and taking the argmax;
* ``bench:arrivals`` -- the load generator waiting for the next request.

A trace is kept as plain event lists (name, start, end in ns), so a small
recorded one can live in the tests as JSON.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench:window"
HOST_SPANS = ("bench:window", "bench:scheduler", "bench:arrivals",
              "sampling")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class DeviceTrace:
    """Per device (plane name): its op and module events; plus the host
    spans of the benchmark."""

    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]

    # -- (de)serialisation ------------------------------------------------
    def to_json(self) -> Dict:
        enc = lambda evs: [[e.name, e.start_ns, e.end_ns] for e in evs]
        return {"ops": {k: enc(v) for k, v in self.ops.items()},
                "modules": {k: enc(v) for k, v in self.modules.items()},
                "host": enc(self.host)}

    @classmethod
    def from_json(cls, d: Dict) -> "DeviceTrace":
        dec = lambda evs: [Event(n, int(s), int(e)) for n, s, e in evs]
        return cls({k: dec(v) for k, v in d["ops"].items()},
                   {k: dec(v) for k, v in d["modules"].items()},
                   dec(d["host"]))

    @classmethod
    def from_xspace(cls, path: str) -> "DeviceTrace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(str(path))
        ops: Dict[str, List[Event]] = {}
        modules: Dict[str, List[Event]] = {}
        host: List[Event] = []
        for plane in pd.planes:
            if _DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops[plane.name] = _events(line.events)
                    elif line.name == "XLA Modules":
                        modules[plane.name] = _events(line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend(e for e in _events(line.events)
                                if e.name in HOST_SPANS
                                or e.name.startswith("step:"))
        host.sort(key=lambda e: e.start_ns)
        return cls(ops, modules, host)

    # -- the window ---------------------------------------------------------
    def window(self) -> Optional[Tuple[int, int]]:
        w = [e for e in self.host if e.name == WINDOW]
        return (w[0].start_ns, w[-1].end_ns) if w else None

    def devices(self) -> List[str]:
        return sorted(d for d, evs in self.ops.items() if evs)


def _events(evs) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in evs]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_intervals(tr: DeviceTrace, device: str) -> List[Tuple[int, int]]:
    """Intervals in the window in which some op ran on ``device``."""
    w = tr.window()
    if w is None:
        return []
    return clip(union([(e.start_ns, e.end_ns) for e in tr.ops[device]]), *w)


def busy_s(tr: DeviceTrace) -> Optional[float]:
    """Seconds of the window in which an op ran, averaged over devices."""
    devs = tr.devices()
    if tr.window() is None or not devs:
        return None
    tot = sum(sum(e - s for s, e in busy_intervals(tr, d)) for d in devs)
    return tot / len(devs) / 1e9


def window_s(tr: DeviceTrace) -> Optional[float]:
    w = tr.window()
    return (w[1] - w[0]) / 1e9 if w else None


def idle_gaps(tr: DeviceTrace, device: str) -> List[Tuple[str, int]]:
    """Each idle gap of the window as (what the host was doing, ns): the
    innermost benchmark span open at the gap's midpoint."""
    w = tr.window()
    if w is None:
        return []
    busy = busy_intervals(tr, device)
    edges = [w[0]] + [x for iv in busy for x in iv] + [w[1]]
    spans = [e for e in tr.host if e.name != WINDOW]
    out = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) // 2
        open_ = [h for h in spans if h.start_ns <= mid < h.end_ns]
        label = max(open_, key=lambda h: h.start_ns).name if open_ \
            else "outside steps"
        out.append((label, e - s))
    return out


def step_modules(tr: DeviceTrace, device: str,
                 dispatched: Optional[Sequence[str]] = None
                 ) -> Optional[List[Tuple[str, Event]]]:
    """Pair the device's executions of the jitted model steps with the
    host's ``step:<which>`` spans, in order.

    The engine's five steps are anonymous lambdas, so their modules are
    told apart from the small eager programs (table updates, argmax) by the
    ``lambda`` in their name, and from each other only by order: one device
    stream runs them in the order they were enqueued. The profiler can miss
    the first events after it starts, never the last before it stops (the
    benchmark waits for the device first), so the two lists are aligned
    from their ends; every module must start after its span does. With
    ``dispatched`` (the steps the benchmark recorded, in order), its tail
    must name the same steps. None where any of this fails: the readers
    then report nothing."""
    spans = [h for h in tr.host if h.name.startswith("step:")]
    mods = sorted((m for m in tr.modules.get(device, [])
                   if "lambda" in m.name), key=lambda m: m.start_ns)
    k = min(len(spans), len(mods))
    if k == 0:
        return None
    spans, mods = spans[len(spans) - k:], mods[len(mods) - k:]
    if any(s.start_ns > m.start_ns for s, m in zip(spans, mods)):
        return None
    which = [s.name[len("step:"):] for s in spans]
    if dispatched is not None and list(dispatched[len(dispatched) - k:]) \
            != which:
        return None
    return list(zip(which, mods))


def op_time_ns(tr: DeviceTrace, device: str, pattern: str,
               within: Optional[Sequence[Event]] = None) -> int:
    """Summed device time of the ops whose name matches ``pattern``: over
    the whole trace, or only those that start inside one of the events
    ``within`` (the step executions a cost was counted for)."""
    rx = re.compile(pattern)
    ops = [e for e in tr.ops.get(device, []) if rx.search(e.name)]
    if within is not None:
        spans = sorted((m.start_ns, m.end_ns) for m in within)
        starts = [a for a, _ in spans]
        keep = []
        for e in ops:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < spans[i][1]:
                keep.append(e)
        ops = keep
    return sum(e.dur_ns for e in ops)


# XLA op names are the whole HLO instruction. The Pallas kernels are
# custom calls to "tpu_custom_call", told apart by their operands.
_OPND = r"\{[^}]*\} %[^,\s)]+"
GEMM_OP = (r"= bf16\[\d+,\d+\]\{[^}]*\} custom-call\("
           rf"bf16\[\d+,\d+\]{_OPND}, bf16\[\d+,\d+\]{_OPND}, "
           rf"f32\[\d+,\d+\]{_OPND}\), "
           r'custom_call_target="tpu_custom_call"')
PAGED_DECODE_OP = (r"= bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} custom-call\("
                   rf"s32\[\d+\]{_OPND}, s32\[\d+\]{_OPND}, "
                   rf"bf16\[\d+,\d+,\d+,\d+\]{_OPND}, "
                   rf"bf16\[\d+,\d+,\d+,\d+\]{_OPND}, "
                   rf"bf16\[\d+,\d+,\d+,\d+\]{_OPND}\), "
                   r'custom_call_target="tpu_custom_call"')


_CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name: str) -> str:
    """A short, stable label for an XLA op event, whose name is the whole
    HLO instruction: the instruction's name without its numeric suffix,
    or for a custom call its target and result shape."""
    head = name.split(" = ", 1)[0].lstrip("%")
    head = re.sub(r"[.\d]+$", "", head)
    m = _TARGET.search(name)
    if m:
        shape = name.split(" = ", 1)[1].split(" ", 1)[0] if " = " in name \
            else ""
        return f"{m.group(1)} {re.sub(r'{[^}]*}', '', shape)}"
    return head


def top_ops(tr: DeviceTrace, device: str, n: int = 10) -> List[List]:
    """The ``n`` op labels that took most device time in the window, as
    [label, seconds]. Loops and calls, which contain other ops, are left
    out."""
    w = tr.window()
    if w is None:
        return []
    tot: Dict[str, int] = {}
    for e in tr.ops.get(device, []):
        s, t = max(e.start_ns, w[0]), min(e.end_ns, w[1])
        if t > s and not _CONTAINER.match(e.name):
            k = op_label(e.name)
            tot[k] = tot.get(k, 0) + (t - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_by_activity(tr: DeviceTrace, device: str, n: int = 10) -> List[List]:
    """Idle seconds of the window summed by what the host was doing, the
    largest first, as [activity, seconds]."""
    tot: Dict[str, int] = {}
    for label, ns in idle_gaps(tr, device):
        tot[label] = tot.get(label, 0) + ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def find_xspace(root: str) -> Optional[Path]:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    return found[-1] if found else None


def save(tr: DeviceTrace, path: str) -> None:
    Path(path).write_text(json.dumps(tr.to_json()))
