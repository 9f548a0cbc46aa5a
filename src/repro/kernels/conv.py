"""Conv2D as an implicit-im2col GEMM Pallas kernel.

The shipped Gemmini design does im2col on the *host* CPU, and the paper's
own DSE shows that host-side work caps whole-network speedup (MobileNet:
330x on layer 1, 6x end-to-end). Section 7 proposes mapping convolutions to
GEMMs *transparently in hardware*; this kernel is that future-work item,
adapted to the TPU memory hierarchy: the im2col patch matrix is never
materialized in HBM -- patch rows are sliced out of the (VMEM-resident)
input block inside the kernel and fed straight to the MXU, with the
Gemmini accumulate/round-shift/saturate/activation epilogue fused.

Schedule: grid = (N, CO_tiles, KH*KW) with the filter-tap axis innermost
("arbitrary"): the (OH*OW, co_t) accumulator tile is output-stationary in
VMEM across the tap stream (each tap contributes one (OH*OW, CI) x
(CI, co_t) GEMM), and the epilogue runs on the last tap -- the OS dataflow
of the GEMM engine, re-applied at the convolution level. ``co_tile`` is the
kernel's tunable schedule parameter (``tune.schedules.ConvSchedule``);
``ctx.conv2d(fused=True)`` resolves it through the flag-gated tuner.

Fusion audit note (ROADMAP): the epilogue is fused (the accumulator never
round-trips HBM -- rescale/saturate/activation run in-kernel on the last
tap), and the bias load is hoisted out of the tap stream: the bias operand
only exists when a bias does, and its block index is tap-invariant.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import Activation, GemminiConfig
from repro.kernels import epilogue as epi
from repro.kernels.contracts import kernel_contract


def _conv_kernel(*refs,
                 kh: int, kw: int, oh: int, ow: int, stride: int,
                 acc_dtype, out_dtype, shift: int, activation: Activation,
                 has_bias: bool):
    # The bias operand exists only when a bias does: no zeros block is
    # streamed through the tap stream for bias-free convs, and when present
    # its BlockSpec index (0, cc) is tap-invariant, so the load is hoisted
    # out of the tap stream (Mosaic's block revisiting elides the re-copy;
    # the ref is only read on tap 0).
    if has_bias:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
    tap = pl.program_id(2)
    i = tap // kw
    j = tap % kw

    @pl.when(tap == 0)
    def _init():
        if has_bias:
            acc_ref[...] = jnp.broadcast_to(
                b_ref[...].astype(acc_dtype), acc_ref.shape)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # slice the (i, j) tap's patch rows from the padded input block:
    # rows r of the output sample input row i + r*stride.
    x = x_ref[0]                                    # (HP, WP, CI)
    hp, wp, ci = x.shape
    xi = jax.lax.dynamic_slice(
        x, (i, j, 0), (hp - kh + 1, wp - kw + 1, ci))
    if stride > 1:
        xi = jax.lax.slice(xi, (0, 0, 0), xi.shape, (stride, stride, 1))
    patch = xi.reshape(oh * ow, ci)
    w = w_ref[0]                                    # (CI, co_t)
    acc_ref[...] += jax.lax.dot_general(
        patch, w, (((1,), (0,)), ((), ())), preferred_element_type=acc_dtype)

    @pl.when(tap == kh * kw - 1)
    def _flush():
        o_ref[0] = epi.apply(acc_ref[...], shift=shift, activation=activation,
                             out_dtype=out_dtype).reshape(oh, ow, -1)


@kernel_contract("conv2d_implicit")
def conv2d_implicit(x: jnp.ndarray, w: jnp.ndarray,
                    b: Optional[jnp.ndarray] = None, *, cfg: GemminiConfig,
                    stride: int = 1, padding: int = 0, shift: int = 0,
                    activation: Activation = Activation.NONE,
                    co_tile: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """x: (N, H, W, CI) , w: (KH, KW, CI, CO) -> (N, OH, OW, CO).

    The input image block lives in VMEM for the whole tap stream; the output
    accumulator is resident at ``cfg.acc_dtype`` width (the Gemmini
    accumulator SRAM); rescale/saturate/activation are fused on the last tap.
    """
    n, h, wd, ci = x.shape
    kh, kw, ci2, co = w.shape
    assert ci == ci2, (ci, ci2)
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (wd + 2 * padding - kw) // stride + 1
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
    hp, wp = x.shape[1], x.shape[2]
    # trim any excess rows/cols beyond what the taps need (exact cover)
    need_h = (oh - 1) * stride + kh
    need_w = (ow - 1) * stride + kw
    x = x[:, :need_h, :need_w]
    hp, wp = need_h, need_w

    co_tile = min(co_tile, co)
    nco = -(-co // co_tile)
    pad_co = nco * co_tile - co
    wm = w.reshape(kh * kw, ci, co)
    if pad_co:
        wm = jnp.pad(wm, ((0, 0), (0, 0), (0, pad_co)))
    has_bias = b is not None

    kernel = functools.partial(
        _conv_kernel, kh=kh, kw=kw, oh=oh, ow=ow, stride=stride,
        acc_dtype=cfg.acc_jnp, out_dtype=cfg.output_jnp, shift=shift,
        activation=activation, has_bias=has_bias)

    in_specs = [
        pl.BlockSpec((1, hp, wp, ci), lambda nn, cc, tt: (nn, 0, 0, 0)),
        pl.BlockSpec((1, ci, co_tile), lambda nn, cc, tt: (tt, 0, cc)),
    ]
    operands = [x, wm]
    if has_bias:
        # Tap-invariant index: the bias tile for output-channel block cc is
        # fetched once per (n, cc), not once per filter tap.
        in_specs.append(pl.BlockSpec((1, co_tile),
                                     lambda nn, cc, tt: (0, cc)))
        operands.append(jnp.pad(b.astype(cfg.acc_jnp), (0, pad_co))[None, :])

    out = pl.pallas_call(
        kernel,
        grid=(n, nco, kh * kw),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, oh, ow, co_tile),
                               lambda nn, cc, tt: (nn, 0, 0, cc)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, nco * co_tile),
                                       cfg.output_jnp),
        scratch_shapes=[pltpu.VMEM((oh * ow, co_tile), cfg.acc_jnp)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[..., :co]
