"""stacked_gemm_roofline: the projection GEMM kernel's share of its
roofline, in percent, as ``gemm_roofline`` defines it (the same cost
functions, the same steps), over the GEMM's calls in the form that reads
its weight from a stack: the Pallas custom calls whose operands are the
prefetched layer index, an s32[1], then two bf16 matrices -- the
activations and the stack's (S*K, N) view, in either dataflow's order --
and an f32 bias. A program whose GEMM takes no layer index makes no such
call, and the reader finds nothing."""

from bench import devtrace
from bench.roofline import roofline_share

KERNEL = (r"= bf16\[\d+,\d+\]\{[^}]*\} custom-call\("
          rf"s32\[1\]{devtrace._OPND}, "
          rf"bf16\[\d+,\d+\]{devtrace._OPND}, bf16\[\d+,\d+\]{devtrace._OPND}, "
          rf"f32\[\d+,\d+\]{devtrace._OPND}\), "
          r'custom_call_target="tpu_custom_call"')


def read(run):
    return roofline_share(run, "gemm", KERNEL)
