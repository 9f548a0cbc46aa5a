"""Observability stack: span tracer, metrics registry, kernel profiler.

The invariants under test (ISSUE 8: full-stack observability):

* **Off by default, bit-exact when off.** A traced engine produces the
  same tokens as an untraced one; every emission site is a None check.
* **Bounded.** The event ring never grows past its capacity; overflow is
  counted, not silently eaten.
* **Well-formed.** Every exported trace validates against the Chrome
  trace event schema (the CI gate `python -m repro.obs --check` runs).
* **Complete.** With tracing on, every request-lifecycle stage —
  including forced preemption and forced fault fallback — lands as an
  event, and the allocator/engine/fault tracks populate.
* **Honest math.** Percentiles over empty populations are None (never a
  fabricated 0.0), and the profiler's contract-derived FLOPs are exact
  for known shapes across all kernel families.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import roofline
from repro.core.config import GemminiConfig
from repro.core.context import ExecutionContext
from repro.models import transformer as tf
from repro.obs import profile as oprofile
from repro.obs import trace as otrace
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import Profiler
from repro.obs.trace import Tracer, req_tid, validate_chrome
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import _pct

_TINY = tf.ModelConfig(name="tiny-serve", family="dense", n_layers=2,
                       d_model=32, vocab=64, n_heads=2, n_kv_heads=1,
                       head_dim=16, d_ff=64, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _no_global_sinks():
    """Tests must not leak a process-global tracer/profiler into each
    other (or into the rest of the suite)."""
    yield
    otrace.deactivate()
    oprofile.deactivate()


def _names(events, cat=None):
    return [e["name"] for e in events
            if cat is None or e.get("cat") == cat]


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------
def test_ring_bounds_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4
    assert tr.dropped == 2
    # oldest evicted first
    assert _names(tr.events) == ["e2", "e3", "e4", "e5"]


def test_injected_clock_deterministic_timestamps():
    t = [100.0]
    tr = Tracer(capacity=16, clock=lambda: t[0])
    t[0] = 100.5
    tr.instant("a")
    t[0] = 101.0
    tr.complete("s", 100.25, 100.75, cat="engine")
    a, s = tr.events
    assert a["ts"] == pytest.approx(0.5e6)
    assert s["ts"] == pytest.approx(0.25e6) and s["dur"] == pytest.approx(0.5e6)


def test_chrome_export_schema_valid(tmp_path):
    tr = Tracer(capacity=64)
    tr.instant("i", cat="alloc", tid=otrace.TID_ALLOC, slot=1)
    with tr.span("work", cat="engine"):
        pass
    tr.counter("arena_pages", used=3, free=5)
    payload = tr.chrome()
    assert validate_chrome(payload) == []
    path = tmp_path / "t.json"
    tr.export_chrome(str(path))
    assert validate_chrome(json.loads(path.read_text())) == []
    # and the JSONL round-trip yields the same events
    jl = tmp_path / "t.jsonl"
    tr.export_jsonl(str(jl))
    assert otrace.load(str(jl)) == list(tr.events)


def test_validator_rejects_malformed_events():
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "pid": 0, "tid": 0},   # no dur
        {"name": "y", "ph": "??", "ts": 0, "pid": 0, "tid": 0},  # bad phase
        {"ph": "i", "ts": 0, "pid": 0, "tid": 0},                # no name
    ]}
    errs = validate_chrome(bad)
    assert len(errs) == 3
    assert validate_chrome("nope") and validate_chrome({"foo": 1})


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_label_aggregation():
    m = MetricsRegistry()
    m.counter("retries", site="decode").inc()
    m.counter("retries", site="decode").inc()
    m.counter("retries", site="prefill").inc()
    assert m.value("retries") == 3.0
    assert m.counters_flat() == {"retries": 3.0}
    snap = m.snapshot()
    assert snap["counters"]["retries{site=decode}"] == 2.0


def test_gauge_peaks_and_series():
    m = MetricsRegistry(gauge_series=8)
    for t, v in enumerate((2, 7, 3)):
        m.gauge("arena_used_pages").set(v, t=float(t))
    assert m.gauge_peak("arena_used_pages") == 7
    assert m.gauge_peaks() == {"arena_used_pages_peak": 7}
    assert list(m.gauge("arena_used_pages").series) == [
        (0.0, 2), (1.0, 7), (2.0, 3)]


def test_histogram_empty_percentile_is_none():
    m = MetricsRegistry()
    h = m.histogram("latency_s")
    assert h.percentile(50) is None and h.mean is None
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.percentile(50) == pytest.approx(2.5)
    assert h.percentile(100) == 4.0 and h.mean == 2.5


def test_summarize_percentiles_none_for_empty_population():
    assert _pct([], 50) is None
    assert _pct([3.0], 99) == 3.0
    # engine-level: a run with zero requests must report null percentiles,
    # not fabricated 0.0s (the old `or [0.0]` bug)
    eng = ServingEngine(_TINY, max_slots=1, max_context=32, page_size=8,
                        n_pages=4, temperature=0.0, seed=0)
    s = eng.run()["summary"]
    assert s["requests"] == 0
    for k in ("p50_latency_s", "p99_latency_s", "p50_ttft_s",
              "p99_ttft_s", "p50_itl_s", "p95_itl_s"):
        assert s[k] is None, k


# ---------------------------------------------------------------------------
# engine integration: bit-exactness + lifecycle completeness
# ---------------------------------------------------------------------------
def _engine(trace=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_context", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("n_pages", 8)
    return ServingEngine(_TINY, temperature=0.0, seed=0, trace=trace, **kw)


def _run_tokens(eng, rng, lens=(5, 9), gen=4):
    for n in lens:
        eng.submit(rng.integers(0, 64, (n,), dtype=np.int32), gen)
    rep = eng.run()
    return [np.asarray(r["tokens"]).ravel() for r in rep["requests"]], rep


def test_traced_engine_bit_identical_tokens():
    params = tf.init_params(jax.random.PRNGKey(3), _TINY)
    plain, _ = _run_tokens(_engine(params=params),
                           np.random.default_rng(0))
    traced_eng = _engine(trace=True, params=params)
    traced, _ = _run_tokens(traced_eng, np.random.default_rng(0))
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)
    assert traced_eng.tracer is not None and len(traced_eng.tracer.events)


def test_lifecycle_events_under_forced_preemption():
    # Starved arena (the test_engine_correct_under_eviction geometry):
    # preemption-by-eviction must fire, and every stage must land.
    rng = np.random.default_rng(0)
    eng = _engine(trace=True, n_pages=4)
    for n, g in zip((7, 9, 6), (10, 9, 8)):
        eng.submit(rng.integers(0, 64, (n,), dtype=np.int32), g)
    rep = eng.run()
    assert rep["summary"]["preemptions"] > 0
    evs = list(eng.tracer.events)
    req_names = set(_names(evs, cat="request"))
    assert {"submitted", "queued", "preempt", "token", "decode",
            "finished"} <= req_names
    assert any(n.startswith("prefill") for n in req_names)
    assert {"alloc", "evict"} <= set(_names(evs, cat="alloc"))
    assert "step" in _names(evs, cat="engine")
    assert "arena_pages" in _names(evs, cat="metrics")
    # one lane per request, and every request's lane has a terminal event
    for rid in range(3):
        lane = [e for e in evs if e["tid"] == req_tid(rid)]
        assert "finished" in [e["name"] for e in lane]
    # registry agrees with the trace
    assert eng.metrics.value("preemptions") == rep["summary"]["preemptions"]
    assert validate_chrome(eng.tracer.chrome()) == []


def test_lifecycle_events_under_forced_fallback():
    # A NaN-poisoned decode forces the xla_twin fallback; the fault firing
    # and the fallback must both land on their tracks.
    rng = np.random.default_rng(0)
    eng = _engine(trace=True, backend="interpret", prefill_chunk=8,
                  faults="seed=1;nan@decode:max=1")
    for n in (5, 11):
        eng.submit(rng.integers(0, 64, (n,), dtype=np.int32), 4)
    rep = eng.run()
    assert rep["summary"]["fallbacks"] == 1
    evs = list(eng.tracer.events)
    assert "fallback" in _names(evs, cat="engine")
    assert "fault:nan" in _names(evs, cat="fault")
    assert eng.counters["fallbacks"] == 1          # compat view intact


def test_hang_report_dumps_diagnostics():
    rng = np.random.default_rng(0)
    eng = _engine(trace=True)
    eng.submit(rng.integers(0, 64, (5,), dtype=np.int32), 4)
    eng.max_run_iters = 1
    with pytest.raises(RuntimeError) as exc:
        eng.run()
    msg = str(exc.value)
    assert "did not converge" in msg
    for needle in ("queue", "arena", "counters", "slot"):
        assert needle in msg, needle


# ---------------------------------------------------------------------------
# kernel profiler
# ---------------------------------------------------------------------------
_CFG = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                     output_dtype="bf16")


def _profiled_ctx():
    prof = Profiler()
    oprofile.install(prof)
    ctx = ExecutionContext(cfg=_CFG, backend="interpret", tune_mode="off")
    return prof, ctx


def test_profiler_covers_all_kernel_families():
    """One eager dispatch per kernel family on the interpret backend:
    every bucket must carry a contract join and a utilization verdict."""
    prof, ctx = _profiled_ctx()
    f32, i32 = jnp.float32, jnp.int32
    # gemm + matmul (gemm engine)
    ctx.gemm(jnp.ones((16, 32), jnp.bfloat16), jnp.ones((32, 8), jnp.bfloat16))
    ctx.matmul(jnp.ones((2, 8, 32), jnp.bfloat16),
               jnp.ones((32, 8), jnp.bfloat16))
    # conv2d
    ctx.conv2d(jnp.ones((1, 8, 8, 8), jnp.bfloat16),
               jnp.ones((3, 3, 8, 8), jnp.bfloat16))
    # flash attention
    q = jnp.ones((1, 16, 2, 16), f32)
    k = jnp.ones((1, 16, 1, 16), f32)
    ctx.flash_attention(q, k, k)
    # paged decode + paged prefill
    pool = jnp.zeros((1, 5, 8, 16), f32)
    ctx.paged_attention(jnp.ones((2, 1, 2, 16), f32), pool, pool,
                        jnp.zeros((2, 2), i32), jnp.ones((2,), i32))
    ctx.paged_prefill_attention(jnp.ones((1, 8, 2, 16), f32), pool, pool,
                                jnp.zeros((4,), i32), 0)
    # ssd (mamba-2 mixer)
    x = jnp.ones((1, 32, 2, 16), f32)
    ctx.ssd(x, jnp.ones((1, 32, 2), f32), -jnp.ones((2,), f32),
            jnp.ones((1, 32, 1, 8), f32), jnp.ones((1, 32, 1, 8), f32),
            chunk=16)

    rows = {r["op"]: r for r in prof.snapshot()}
    want = {"gemm", "matmul", "conv2d", "flash_attention",
            "paged_attention", "paged_prefill_attention", "ssd"}
    assert want <= set(rows)
    # The CPU has no published peaks: its timings get no utilization.
    assert prof.peaks is None
    for op in want:
        r = rows[op]
        assert r["contract"], op
        assert r["flops"] > 0 and r["bytes"] > 0, op
        assert r["calls"] == 1 and r["min_s"] is not None, op
        assert r["compute_util"] is None and r["bound"] is None, op
    # Against a chip's peaks every bucket gets a utilization verdict.
    prof.peaks = roofline.peaks("TPU v5 lite")
    rows = {r["op"]: r for r in prof.snapshot()}
    for op in want:
        r = rows[op]
        assert r["compute_util"] is not None and r["compute_util"] >= 0, op
        assert r["bound"] in ("compute", "memory"), op
    # contract-derived FLOPs are exact for known shapes
    assert rows["gemm"]["flops"] == 2.0 * 16 * 8 * 32
    assert rows["matmul"]["flops"] == 2.0 * 16 * 8 * 32
    assert rows["flash_attention"]["flops"] == 4.0 * 1 * 2 * 16 * 16 * 16
    assert "gemm" in prof.report()


def test_profiled_dispatch_values_unchanged():
    a = jnp.asarray(np.random.default_rng(0).standard_normal((16, 32)),
                    jnp.bfloat16)
    b = jnp.asarray(np.random.default_rng(1).standard_normal((32, 8)),
                    jnp.bfloat16)
    plain_ctx = ExecutionContext(cfg=_CFG, backend="xla", tune_mode="off")
    want = np.asarray(plain_ctx.gemm(a, b))
    prof = Profiler()
    oprofile.install(prof)
    got = np.asarray(
        ExecutionContext(cfg=_CFG, backend="xla", tune_mode="off").gemm(a, b))
    np.testing.assert_array_equal(want, got)
    assert next(iter(prof.buckets.values())).calls == 1


def test_profiler_emits_kernel_spans_to_tracer():
    tr = Tracer(capacity=32)
    prof = Profiler(tracer=tr)
    oprofile.install(prof)
    ctx = ExecutionContext(cfg=_CFG, backend="xla", tune_mode="off")
    ctx.gemm(jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.bfloat16))
    spans = [e for e in tr.events if e.get("cat") == "kernel"]
    assert len(spans) == 1 and spans[0]["name"] == "gemm"
    assert spans[0]["args"]["flops"] == 2.0 * 8 * 8 * 8


# ---------------------------------------------------------------------------
# CLI (python -m repro.obs)
# ---------------------------------------------------------------------------
def test_cli_check_exit_codes(tmp_path, capsys):
    from repro.obs.__main__ import main
    tr = Tracer(capacity=32)
    tr.instant("submitted", cat="request", tid=req_tid(0))
    tr.complete("step", tr.clock() - 1e-3, cat="engine")
    good = tmp_path / "good.json"
    tr.export_chrome(str(good))
    assert main([str(good), "--check"]) == 0
    assert "OK" in capsys.readouterr().out

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "pid": 0, "tid": 0}]}))
    assert main([str(bad), "--check"]) == 1
    assert "SCHEMA" in capsys.readouterr().err
    assert main([str(tmp_path / "missing.json")]) == 2


def test_cli_summary_renders(tmp_path, capsys):
    from repro.obs.__main__ import main
    rng = np.random.default_rng(0)
    eng = _engine(trace=True)
    _run_tokens(eng, rng)
    path = tmp_path / "t.json"
    eng.tracer.export_chrome(str(path))
    assert main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "top spans" in out and "req 0" in out
