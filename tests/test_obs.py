"""Observability stack: span tracer, metrics registry, phase spans.

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
  fabricated 0.0).
* **Phase spans always on.** Every step records its host-loop phases in
  the registry, on the engine clock, whether or not the ring is on; with
  the ring on the same spans land there with their parents, and under a
  profiler session on its host plane.
"""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as tf
from repro.obs import trace as otrace
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import SPANS, Tracer, req_tid, validate_chrome
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import _pct

_TINY = tf.ModelConfig(name="tiny-serve", family="dense", n_layers=2,
                       d_model=32, vocab=64, n_heads=2, n_kv_heads=1,
                       head_dim=16, d_ff=64, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _no_global_sinks():
    """Tests must not leak a process-global tracer into each other (or
    into the rest of the suite)."""
    yield
    otrace.deactivate()


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
              "p99_ttft_s", "p50_itl_s", "p95_itl_s", "p50_queue_wait_s",
              "p99_queue_wait_s"):
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
    assert "engine.step" in _names(evs, cat="engine")
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
# phase spans: registry always, ring when on, profiler annotations
# ---------------------------------------------------------------------------
class _TickClock:
    """A clock that advances 1 ms per reading."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def _chunk_and_decode(eng):
    """One step that prefills a chunk and decodes: request 0 is prefilled
    and decoding after the first step; request 1's 11-token prompt takes
    two 8-position chunks."""
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, 64, (5,), dtype=np.int32), 4)
    eng.step()
    eng.submit(rng.integers(0, 64, (11,), dtype=np.int32), 4)
    t0 = eng.now()
    eng.step()
    return t0, eng.now()


def test_step_phases_in_registry_with_ring_off():
    eng = _engine(prefill_chunk=8, clock=_TickClock())
    assert eng.tracer is None
    t0, t1 = _chunk_and_decode(eng)
    obs = {n: eng.metrics.observations(n, t0, t1) for n in SPANS}
    (ts, step), = obs["engine.step"]
    assert ts >= t0 and ts + step <= t1
    for name in SPANS:
        assert obs[name], name
        for t, d in obs[name]:
            assert d > 0 and ts <= t and t + d <= ts + step, name
    # the children are disjoint and lie inside the step
    kids = sum(d for n in SPANS[1:] for _, d in obs[n])
    assert kids <= step
    whiches = {h.labels["which"] for h in eng.metrics._histograms.values()
               if h.name == "engine.dispatch" and h.count}
    assert {"prefill_nl", "decode"} <= whiches


def test_step_counters():
    """What a step counts lives where it was already kept: the
    scheduler's prefill tokens, the chunks in ``summarize()``, the compile
    buckets in ``observed_buckets``, whose growth the dispatch span
    marks."""
    eng = _engine(prefill_chunk=8)
    _chunk_and_decode(eng)
    s = eng.run()["summary"]
    # true cache positions 5 + 11, in three chunks
    assert eng.metrics.value("prefill_tokens") == 16
    assert s["prefill_chunks"] == 3
    new = {h.labels["which"]: 0 for h in eng.metrics._histograms.values()
           if h.name == "engine.dispatch"}
    for h in eng.metrics._histograms.values():
        if h.name == "engine.dispatch" and h.labels["new_bucket"]:
            new[h.labels["which"]] += h.count
    assert new == {w: len(b) for w, b in eng.observed_buckets.items()}
    assert new["decode"] == 1


def test_sampled_rows_slice_is_prep_not_wait():
    """The eager slice of the sampled rows is host work queued behind the
    step, so it lies in ``engine.prep``; ``engine.wait`` holds the sampler
    alone."""
    eng = _engine(prefill_chunk=8)
    seen = {"slice": [], "sample": []}

    class Rows:
        def __init__(self, logits):
            self.logits = logits

        def __getitem__(self, idx):
            seen["slice"].append(eng.spans._open[-1])
            return self.logits[idx]

    run_guarded, sample = eng._run_guarded, eng._sample

    def guarded(*a):
        logits, state = run_guarded(*a)
        return Rows(logits), state

    def sampled(rows):
        seen["sample"].append(eng.spans._open[-1])
        return sample(rows)

    eng._run_guarded, eng._sample = guarded, sampled
    _chunk_and_decode(eng)
    assert seen["slice"] and set(seen["slice"]) == {"engine.prep"}
    assert len(seen["sample"]) == len(seen["slice"])
    assert set(seen["sample"]) == {"engine.wait"}


def test_window_picks_exactly_the_steps_inside():
    eng = _engine(clock=_TickClock())
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, 64, (5,), dtype=np.int32), 6)
    bounds = []
    for _ in range(4):
        a = eng.now()
        eng.step()
        bounds.append((a, eng.now()))
    lo, hi = bounds[1][0], bounds[2][1]
    got = [t for t, d in eng.metrics.observations("engine.step", lo, hi)
           if t + d <= hi]
    assert len(got) == 2
    assert bounds[1][0] < got[0] < got[1] < bounds[2][1]
    assert got[0] < bounds[2][0]
    after = eng.now()
    assert eng.metrics.observations("engine.step", after, after + 1.0) == []
    assert len(eng.metrics.observations("engine.step")) == 4


def test_window_none_once_its_observations_are_dropped():
    """A bounded reservoir that has dropped observations of a window says
    so, rather than handing a reader part of the window for the whole."""
    h = Histogram("engine.plan", {}, capacity=4)
    for i in range(10):
        h.observe(1.0, float(i))
    # the last four kept: 6..9; the newest dropped at 5
    assert h.count == 10 and h.dropped_t == 5.0
    assert h.window(5.0, 9.0) is None
    assert [t for t, _ in h.window(5.5, 8.0)] == [6.0, 7.0, 8.0]
    # untimed observations are dropped without a time
    u = Histogram("ttft", {}, capacity=2)
    for v in range(5):
        u.observe(float(v))
    assert u.dropped_t is None and u.window(0.0, 1.0) == []
    # the registry pools label sets: one incomplete set spoils the window
    m = MetricsRegistry()
    full = m.histogram("engine.plan", which="decode")
    for i in range(full.capacity + 1):
        full.observe(1.0, float(i))
    m.histogram("engine.plan", which="chunk").observe(1.0, 0.5)
    assert m.observations("engine.plan") is None
    assert m.observations("engine.plan", 0.0, 2.0) is None
    assert m.observations("engine.plan", 0.5, 2.0) == [
        (0.5, 1.0), (1.0, 1.0), (2.0, 1.0)]


def test_spans_annotate_only_the_device_engine():
    """The tensor-free control plane and the ring tracer's module need no
    jax; the device-backed engine's spans are profiler annotations."""
    from repro.analysis.mc.harness import MCConfig, NullEngine
    assert NullEngine(MCConfig("spans")).spans.annotation is None
    assert _engine().spans.annotation is jax.profiler.TraceAnnotation
    code = ("import sys, repro.obs, repro.obs.trace, repro.obs.__main__; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def test_ring_gets_the_same_spans_with_parents():
    params = tf.init_params(jax.random.PRNGKey(3), _TINY)
    plain = _engine(params=params, prefill_chunk=8)
    ringed = _engine(params=params, prefill_chunk=8, trace=True)
    toks = [_run_tokens(e, np.random.default_rng(1), lens=(5, 11, 20))[0]
            for e in (plain, ringed)]
    for a, b in zip(*toks):
        np.testing.assert_array_equal(a, b)
    evs = [e for e in ringed.tracer.events
           if e.get("ph") == "X" and e["name"] in SPANS]
    assert {e["name"] for e in evs} == set(SPANS)
    for name in SPANS:
        n_ring = sum(1 for e in evs if e["name"] == name)
        assert n_ring == len(ringed.metrics.observations(name)), name
        assert n_ring == len(plain.metrics.observations(name)), name
    parents = {e["name"]: set() for e in evs}
    for e in evs:
        parents[e["name"]].add(e["args"].get("parent"))
    assert parents.pop("engine.step") == {None}
    assert all(p == {"engine.step"} for p in parents.values()), parents
    disp = [e for e in evs if e["name"] == "engine.dispatch"]
    assert {e["args"]["which"] for e in disp} >= {"prefill_nl", "decode"}
    assert sum(e["args"]["new_bucket"] for e in disp) == sum(
        len(b) for b in ringed.observed_buckets.values())
    payload = ringed.tracer.chrome()
    assert validate_chrome(payload) == []


def test_spans_nest_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(prefill_chunk=8)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, 64, (5,), dtype=np.int32), 3)
    eng.step()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
    path, = tmp_path.rglob("*.xplane.pb")
    host = [e for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name.startswith("engine.")]
    step, = [e for e in host if e.name == "engine.step"]
    end = step.start_ns + step.duration_ns
    inside = {e.name for e in host if e is not step
              and step.start_ns <= e.start_ns
              and e.start_ns + e.duration_ns <= end}
    assert {"engine.plan", "engine.dispatch", "engine.wait"} <= inside
    disp = next(e for e in host if e.name == "engine.dispatch")
    with warnings.catch_warnings():
        # the profiler's stats type lacks __module__ (a jaxlib warning)
        warnings.simplefilter("ignore", DeprecationWarning)
        stats = dict(disp.stats)
    assert stats["which"] == "decode"


def test_queue_wait_from_admission_time():
    clock = _TickClock()
    eng = _engine(max_slots=1, clock=clock)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, 64, (5,), dtype=np.int32), 2)
            for _ in range(2)]
    s = eng.run()["summary"]
    assert all(r.t_admitted is not None for r in reqs)
    # one slot: the second request waits for the first to finish
    w0, w1 = (r.t_admitted - r.submitted_at for r in reqs)
    assert 0 < w0 < w1
    assert s["p50_queue_wait_s"] == pytest.approx((w0 + w1) / 2)
    assert s["p99_queue_wait_s"] <= w1


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
