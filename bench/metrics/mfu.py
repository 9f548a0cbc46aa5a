"""mfu: the operations that the tokens processed in the traced part of the
window require -- every true prompt token and every decoded token through
the projections and attention over its context, and the output head only
for the rows that are sampled -- over the traced span's length times the
chip's bf16 peak, in percent. Padding rows and idle slots count for
nothing."""

from bench import devtrace


def read(run):
    if run.trace is None or not run.traced_dispatches or run.peaks is None:
        return None
    span = devtrace.window_s(run.trace)
    if not span:
        return None
    ops = sum(c["model"] for c in run.costs())
    return 100.0 * ops / (span * run.peaks["bf16_flops"])
