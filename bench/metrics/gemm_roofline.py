"""gemm_roofline: the projection GEMM kernel's share of its roofline, in
percent: the least time the chip could take for the GEMM calls of the
window's steps -- per call, the larger of its operations over the bf16
peak and its bytes over the HBM bandwidth, from the config's own shape
functions -- over the summed device time of the GEMM kernel's ops in the
trace: the Pallas custom calls whose operands are two bf16 matrices and
an f32 bias (``devtrace.GEMM_OP``)."""

from bench import devtrace
from bench.roofline import roofline_share

KERNEL = devtrace.GEMM_OP


def read(run):
    return roofline_share(run, "gemm", KERNEL)
