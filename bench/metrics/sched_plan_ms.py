"""sched_plan_ms: the scheduler's decisions per engine step, in
milliseconds: over the engine steps that start and end inside the window,
the mean of the summed ``engine.plan`` spans in a step (shedding expired
requests, the prefill schedule, decode capacity with any preemption,
finishing rejected requests), read from the engine's own metrics
registry."""

from bench import spans


def read(run):
    steps = spans.per_step(run, "engine.plan")
    if steps is None:
        return None
    return 1e3 * sum(p for _, p in steps) / len(steps)
