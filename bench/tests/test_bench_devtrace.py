"""The reduction from a device trace to busy time, idle share, per-step
device time and kernel time: hand-computed on a tiny trace, and
consistent on a small trace recorded on a TPU v5e."""

import json
import re
from pathlib import Path

import pytest

from bench import devtrace
from bench.devtrace import DeviceTrace, Event

FIXTURE = Path(__file__).parent / "fixtures" / "trace_qwen_chat.json"
DEV = "/device:TPU:0"


def _tiny():
    ops = [Event("%fusion.1 = f32[8] fusion(f32[8] %a)", 0, 10),
           Event("%fusion.2 = f32[8] fusion(f32[8] %b)", 5, 20),
           Event('%closed_call.3 = bf16[8,64] custom-call(bf16[8,32] %x, '
                 'bf16[32,64] %w, f32[1,64] %d), '
                 'custom_call_target="tpu_custom_call"', 30, 40),
           Event("%while.4 = (s32[]) while((s32[]) %t)", 28, 45),
           Event("%copy.5 = f32[8] copy(f32[8] %c)", 60, 70)]
    mods = [Event("jit__lambda(1)", 0, 20), Event("jit__argmax(2)", 22, 24),
            Event("jit__lambda(3)", 28, 45)]
    host = [Event("bench:window", 0, 50), Event("step:prefill", 0, 2),
            Event("sampling", 20, 27), Event("bench:scheduler", 19, 29),
            Event("step:decode", 27, 28), Event("bench:arrivals", 45, 60)]
    return DeviceTrace({DEV: ops}, {DEV: mods}, host)


def test_union_and_clip():
    assert devtrace.union([(5, 20), (0, 10), (30, 40), (40, 41)]) == \
        [(0, 20), (30, 41)]
    assert devtrace.clip([(0, 20), (30, 41)], 10, 35) == [(10, 20), (30, 35)]


def test_busy_and_idle_by_hand():
    tr = _tiny()
    # busy in [0, 50): [0, 20) and [28, 45) -> 37 ns; the copy at 60 is
    # outside the window
    assert devtrace.busy_s(tr) == pytest.approx(37e-9)
    assert devtrace.window_s(tr) == pytest.approx(50e-9)
    gaps = devtrace.idle_gaps(tr, DEV)
    # [20, 28): midpoint 24, inside sampling (20-27) and the scheduler
    # (19-29): the innermost, the later-opened, is sampling;
    # [45, 50): midpoint 47, the load generator waiting
    assert gaps == [("sampling", 8), ("bench:arrivals", 5)]


def test_steps_pair_in_order_and_kernels_by_name():
    tr = _tiny()
    pairs = devtrace.step_modules(tr, DEV, ["prefill", "decode"])
    assert [(w, m.dur_ns) for w, m in pairs] == [("prefill", 20),
                                                 ("decode", 17)]
    # the steps the benchmark recorded must end with the traced ones
    assert devtrace.step_modules(tr, DEV, ["chunk", "prefill", "decode"])
    assert devtrace.step_modules(tr, DEV, ["decode", "prefill"]) is None
    # a module that starts before its dispatch pairs nothing
    tr.host[1] = Event("step:prefill", 1, 2)
    assert devtrace.step_modules(tr, DEV) is None
    assert devtrace.op_time_ns(tr, DEV, "tpu_custom_call") == 10
    top = dict(devtrace.top_ops(tr, DEV))
    assert "while" not in top                     # a container, left out
    assert top["fusion"] == pytest.approx(25e-9)  # both fusions, in window
    assert top["tpu_custom_call bf16[8,64]"] == pytest.approx(10e-9)


def test_json_round_trip(tmp_path):
    tr = _tiny()
    devtrace.save(tr, tmp_path / "t.json")
    back = DeviceTrace.from_json(json.loads((tmp_path / "t.json").read_text()))
    assert back == tr


def _recorded():
    return DeviceTrace.from_json(json.loads(FIXTURE.read_text()))


def test_recorded_decode_step():
    """One qwen1.5-4b decode step at 8 slots, recorded on a TPU v5e by a
    traced run of ``qwen1.5-4b.chat`` (op names shortened, layouts elided
    from the custom calls)."""
    tr = _recorded()
    dev = tr.devices()[0]
    pairs = devtrace.step_modules(tr, dev, ["chunk", "decode"])
    assert [(w, m.dur_ns) for w, m in pairs] == [("decode", 88590598)]
    within = [m for _, m in pairs]
    # 7 projections x 40 layers and the head; one paged kernel per layer
    n = lambda rx: sum(1 for e in tr.ops[dev] if re.search(rx, e.name))
    assert n(devtrace.GEMM_OP) == 281
    assert n(devtrace.PAGED_DECODE_OP) == 40
    assert devtrace.op_time_ns(tr, dev, devtrace.GEMM_OP, within) == 10862303
    assert devtrace.op_time_ns(tr, dev, devtrace.PAGED_DECODE_OP,
                               within) == 29305901
    busy, win = devtrace.busy_s(tr), devtrace.window_s(tr)
    assert 0 < busy <= win
    assert busy == pytest.approx(0.088590262)
    idle = dict(devtrace.idle_by_activity(tr, dev))
    assert sum(idle.values()) == pytest.approx(win - busy, abs=1e-9)
    assert set(idle) <= {"sampling", "step:decode", "bench:scheduler"}


def test_recorded_gemm_roofline_below_peak():
    """The decode step's GEMMs from the benchmark's own shape functions,
    over the recorded kernel time: weight-bound and under 100%."""
    from bench import harness
    from bench.roofline import bound_s
    cfg = json.loads((harness.BENCH / "configs" / "qwen1.5-4b.json")
                     .read_text())
    mod = harness.load_module(harness.BENCH / "configs" / "qwen1.5-4b.py",
                              "qwen")
    peaks = json.loads((harness.BENCH / "peaks.json").read_text()
                       )["chips"]["TPU v5 lite"]
    costs = mod.step_costs(cfg, {"which": "decode", "rows": 8, "page": 64,
                                 "keys": [300] * 5})
    least = sum(bound_s(f, b, peaks) for f, b in costs["gemm"])
    share = 100.0 * least / 10862303e-9
    assert 60.0 < share < 100.0
