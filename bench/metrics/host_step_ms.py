"""host_step_ms: the host's own time per engine step, in milliseconds:
over the engine steps that start and end inside the window, the mean of
the step's span less its ``engine.wait`` spans (the host blocked on the
device for logits), read from the engine's own metrics registry. While
the host does this work the device has nothing queued."""

from bench import spans


def read(run):
    steps = spans.per_step(run, "engine.wait")
    if steps is None:
        return None
    return 1e3 * sum(d - w for d, w in steps) / len(steps)
