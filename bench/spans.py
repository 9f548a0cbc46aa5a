"""The engine's own phase spans, reduced to one number per engine step.

The serving engine records each step of its host loop and the phases in
it (``engine.step`` and its children ``engine.plan``, ``engine.prep``,
``engine.dispatch``, ``engine.wait``, ``engine.commit``) as timed
observations in its metrics registry: ``(start, seconds)`` on the engine
clock, the clock ``Run.t0`` and ``Run.t1`` are on. An engine whose
registry keeps no timed observations gives None, and so does a registry
that has dropped observations of the window.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

STEP = "engine.step"


def per_step(run, child: str) -> Optional[List[Tuple[float, float]]]:
    """For each engine step that starts and ends inside the window, its
    seconds and the summed seconds of the ``child`` spans inside it."""
    observations = getattr(run.engine.metrics, "observations", None)
    if observations is None:
        return None
    steps = observations(STEP, run.t0, run.t1)
    kids = observations(child, run.t0, run.t1)
    if steps is None or kids is None:
        return None
    steps = [(t, d) for t, d in steps if t + d <= run.t1]
    out = []
    i = 0
    for t, d in steps:
        while i < len(kids) and kids[i][0] < t:
            i += 1
        s = 0.0
        while i < len(kids) and kids[i][0] <= t + d:
            s += kids[i][1]
            i += 1
        out.append((d, s))
    return out or None
