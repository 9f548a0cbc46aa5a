"""The program's phase spans as the benchmark reads them: ``host_step_ms``
and ``sched_plan_ms`` from a toy decode run's engine registry, nothing
from an empty window, from an engine that records no spans or from a
registry that dropped the window's spans, and span names apart from the
benchmark's own host spans."""

import types

import pytest

from bench import devtrace, harness, traffic
from bench.tests import toy
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import SPANS

SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def decode_run():
    with pytest.MonkeyPatch.context() as mp:
        cell, mc = toy.toy_cell("decode")
        toy.patch(mp, mc)
        params = harness.make_params(cell, SEED)
        engine = harness.make_engine(cell, mc, params)
        harness.warm_up(engine, SEED)
        items = traffic.generate(cell.mix, SEED, 2.0, mc.vocab)
        served, t0, t1 = harness.serve(cell, engine, items, 2.0,
                                       harness.Hooks(engine, 2.0, None))
        return harness.Run(cell, 2.0, t0, t1, served, engine)


def _read(name, run):
    return harness.metric_reader(name).read(run)


def test_host_step_and_plan_from_a_decode_run(decode_run):
    host = _read("host_step_ms", decode_run)
    plan = _read("sched_plan_ms", decode_run)
    assert host is not None and plan is not None
    assert 0 < plan <= host
    # the host's own time is a part of the step
    steps = decode_run.engine.metrics.observations(
        "engine.step", decode_run.t0, decode_run.t1)
    assert host < 1e3 * max(d for _, d in steps)


def test_nothing_from_an_empty_window_or_without_spans(decode_run):
    after = decode_run.engine.now()
    empty = harness.Run(decode_run.cell, 0.0, after, after + 1.0,
                        decode_run.served, decode_run.engine)
    assert _read("host_step_ms", empty) is None
    assert _read("sched_plan_ms", empty) is None
    bare = types.SimpleNamespace(engine=types.SimpleNamespace(
        metrics=object()), t0=decode_run.t0, t1=decode_run.t1)
    assert _read("host_step_ms", bare) is None
    assert _read("sched_plan_ms", bare) is None


@pytest.mark.parametrize("name", ["host_step_ms", "sched_plan_ms"])
def test_nothing_once_the_registry_dropped_the_window(name):
    """A registry holds a 51-s window of 1-ms steps with three
    ``engine.plan`` and three ``engine.wait`` spans each; a window too long
    for it gives None, not a mean that counts the dropped spans as 0."""
    def run(steps):
        reg = MetricsRegistry()
        step, plan, wait = (reg.histogram(n) for n in
                            ("engine.step", "engine.plan", "engine.wait"))
        for i in range(steps):
            t = 1.0 + 1e-3 * i
            step.observe(1e-3, t)
            for k in range(3):
                plan.observe(1e-4, t + 1e-4 * k)
                wait.observe(1e-4, t + 4e-4 + 1e-4 * k)
        return types.SimpleNamespace(
            engine=types.SimpleNamespace(metrics=reg), t0=1.0,
            t1=1.0 + 1e-3 * steps)

    assert 3 * 51_000 < Histogram.capacity < 3 * 90_000
    want = {"host_step_ms": 0.7, "sched_plan_ms": 0.3}[name]
    assert _read(name, run(51_000)) == pytest.approx(want)
    assert _read(name, run(90_000)) is None


def test_span_names_apart_from_the_benchmarks(decode_run):
    """The device-trace reduction keeps host spans by name; no program
    span may be taken for one of the benchmark's."""
    names = set(SPANS) | {h.name for h in
                          decode_run.engine.metrics._histograms.values()}
    assert set(SPANS) <= names
    for n in names:
        assert n not in devtrace.HOST_SPANS, n
        assert not n.startswith(("step:", "bench:")), n
    assert all(n.startswith("engine.") for n in SPANS)
