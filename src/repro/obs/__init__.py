"""Observability substrate: span tracing, metrics, phase spans.

Two cooperating pieces (docs/observability.md):

- :mod:`repro.obs.trace` — ring-buffered span tracer (request lifecycle,
  engine step phases, allocator/tuner/fault events) with Chrome-trace /
  JSONL export, off by default (``GEMMINI_TRACE`` /
  ``ServingEngine(trace=)`` / ``serve --trace`` enable it); and
  :class:`~repro.obs.trace.Spans`, the serving engine's always-on phase
  spans, which land as ``jax.profiler`` annotations on the device trace's
  clock and as timed registry observations.
- :mod:`repro.obs.metrics` — labelled counters/gauges/histograms; the
  one schema behind ``engine.summarize()`` and the trace's counter tracks.

``python -m repro.obs <trace.json>`` summarizes an exported trace;
``serve --profile DIR`` writes a profiler session with the device ops and
the phase spans.
"""

from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import Spans, Tracer, req_tid, validate_chrome

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Spans", "Tracer", "req_tid", "validate_chrome",
]
