"""paged_attn_roofline: the paged decode attention kernel's share of its
roofline, in percent: per decode step, the larger of the operations and
the whole pages of keys and values its live slots require over the chip's
peaks, over the summed device time of the kernel's ops in the trace: the
Pallas custom calls that take a block table and lengths and a 4-D query
(``devtrace.PAGED_DECODE_OP``)."""

from bench import devtrace
from bench.roofline import roofline_share

KERNEL = devtrace.PAGED_DECODE_OP


def read(run):
    return roofline_share(run, "paged_attn", KERNEL)
