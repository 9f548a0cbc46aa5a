"""Gemmini-generated tiled GEMM as Pallas TPU kernels.

This is the elaborated "systolic array instance": ``C = A @ B + D`` with the
paper's two dataflows, datatype genericity (int8->int32 quantized path and
bf16/fp32 float paths), fused bias, fused activation, and the
rounding/saturating-bitshift output scaling of the quantized datapath.

Dataflow mapping (DESIGN.md section 2):

* **OS (output-stationary)** -- grid (gm, gn, gk) with K innermost
  ("arbitrary" semantics). The C tile lives in a wider-bitwidth VMEM
  accumulator scratch across the K stream (the PE-resident accumulators of
  the paper), and the epilogue -- rounding bitshift, saturation, activation --
  is applied *inside the kernel* on the last K step ("within PEs (for the
  output-stationary dataflow)").

* **WS (weight-stationary)** -- weight-major grid (gn, gm, gk): all the work
  under one weight column strip (fixed j) completes before the next weight
  tiles are touched -- the preloaded PE weight buffer's schedule. Partial
  sums accumulate in a VMEM accumulator scratch across the K stream (the
  paper's accumulator-SRAM-with-input-adders), and the epilogue is fused on
  the last K step "at the output of the accumulator (for the
  weight-stationary dataflow)" -- a single pallas_call, so the int32
  accumulator never round-trips HBM. A bias D is applied by initializing
  the accumulator with it ("executing a mvin into the accumulator");
  ``accumulator_epilogue`` remains as the explicit-mvout API for callers
  that hold a raw accumulator.

Both kernels double-buffer streamed operands through the Pallas grid pipeline
(pipeline_depth=2 in the generator config); pipeline_depth=1 ("fully
combinational" analogue) is emulated by forcing a serial grid.

The weight operand is a stack: both kernels take B as the free ``(S*K, N)``
view of an ``(S, K, N)`` stack plus a scalar-prefetched layer index, and the
weight BlockSpec maps block ``(layer*gk + kk, j)``. A layer scan hands the
GEMM every layer's weights in one buffer and the index of its own, so no
layer's matrix is sliced out and copied before the kernel reads it; a plain
``(K, N)`` weight is a stack of one at index 0.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import Activation, Dataflow, GemminiConfig
from repro.core.tiling import TilePlan
from repro.kernels import epilogue as epi
from repro.kernels.contracts import kernel_contract


# ---------------------------------------------------------------------------
# Output-stationary kernel
# ---------------------------------------------------------------------------
def _os_kernel(li_ref, a_ref, b_ref, d_ref, c_ref, acc_ref, *, nk: int,
               acc_dtype, out_dtype, shift: int, activation: Activation,
               has_bias: bool):
    del li_ref                            # read by the weight's index map
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if has_bias:
            # D is preloaded into the PE accumulators (paper fig. 4, step 1).
            acc_ref[...] = d_ref[...].astype(acc_dtype)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=acc_dtype
    )

    @pl.when(k == nk - 1)
    def _flush():
        c_ref[...] = epi.apply(acc_ref[...], shift=shift,
                               activation=activation, out_dtype=out_dtype)


def _weight_stack(b: jnp.ndarray, layer, k: int, n: int):
    """The weight as the free ``(S*K, N)`` view of its ``(S, K, N)`` stack
    (a ``(K, N)`` weight is a stack of one) and ``layer`` as the int32[1]
    the kernel prefetches, clamped into the stack as a dynamic slice
    clamps its start."""
    assert b.shape[-2:] == (k, n) and b.ndim in (2, 3), (b.shape, k, n)
    s = b.shape[0] if b.ndim == 3 else 1
    li = jnp.clip(jnp.asarray(layer, jnp.int32), 0, s - 1).reshape((1,))
    return b.reshape(s * k, n), li


@kernel_contract("gemm_os")
def gemm_os(a: jnp.ndarray, b: jnp.ndarray, d: Optional[jnp.ndarray],
            plan: TilePlan, cfg: GemminiConfig, *, layer=0, shift: int = 0,
            activation: Activation = Activation.NONE,
            interpret: bool = False) -> jnp.ndarray:
    """Output-stationary GEMM on padded operands (shapes divide the tiles).

    ``b``: the ``(K, N)`` weight, or an ``(S, K, N)`` stack read in place
    at ``layer`` (an int32 scalar, may be traced)."""
    m, n, k = plan.m, plan.n, plan.k
    tm, tn, tk = plan.tile_m, plan.tile_n, plan.tile_k
    gm, gn, gk = plan.grid
    assert a.shape == (m, k), (a.shape, plan)
    bs, li = _weight_stack(b, layer, k, n)
    has_bias = d is not None
    if not has_bias:
        d = jnp.zeros((1, n), cfg.acc_jnp)  # placeholder operand (never read)

    kernel = functools.partial(
        _os_kernel, nk=gk, acc_dtype=cfg.acc_jnp, out_dtype=cfg.output_jnp,
        shift=shift, activation=activation, has_bias=has_bias)

    # pipeline_depth=1 emulation: make every axis "arbitrary" (serial), which
    # disables cross-iteration overlap in the Mosaic pipeline.
    if cfg.pipeline_depth == 1:
        semantics = ("arbitrary", "arbitrary", "arbitrary")
    else:
        semantics = ("parallel", "parallel", "arbitrary")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk, li: (i, kk)),
            pl.BlockSpec((tk, tn), lambda i, j, kk, li: (li[0] * gk + kk, j)),
            pl.BlockSpec((tm if has_bias else 1, tn),
                         (lambda i, j, kk, li: (i, j)) if has_bias
                         else (lambda i, j, kk, li: (0, j))),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, li: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), cfg.acc_jnp)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), cfg.output_jnp),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(li, a, bs, d)


# ---------------------------------------------------------------------------
# Weight-stationary kernel
# ---------------------------------------------------------------------------
def _ws_kernel(li_ref, b_ref, a_ref, d_ref, c_ref, acc_ref, *, nk: int,
               acc_dtype, out_dtype, shift: int, activation: Activation,
               has_bias: bool):
    del li_ref                            # read by the weight's index map
    # Weight-major traversal: all work under one weight column strip (fixed
    # j) completes before the next weight tiles are touched. Partial sums
    # live in the VMEM accumulator scratch across the K stream -- the
    # accumulator-SRAM-with-input-adders of the paper. (The seed's
    # accumulate-through-aliased-HBM-io pattern was unsound for k_steps > 1:
    # Pallas does not guarantee read-after-write through an input/output
    # alias across separated grid revisits.)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _preload():
        if has_bias:
            # "executing a mvin into the accumulator" (paper: WS bias path).
            acc_ref[...] = d_ref[...].astype(acc_dtype)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype)

    @pl.when(kk == nk - 1)
    def _flush():
        # Epilogue "at the output of the accumulator" (paper: WS scaling
        # location), fused on the last K step so the accumulator never takes
        # an HBM round-trip through a separate epilogue pass.
        c_ref[...] = epi.apply(acc_ref[...], shift=shift,
                               activation=activation, out_dtype=out_dtype)


@kernel_contract("gemm_ws")
def gemm_ws(a: jnp.ndarray, b: jnp.ndarray, d: Optional[jnp.ndarray],
            plan: TilePlan, cfg: GemminiConfig, *, layer=0, shift: int = 0,
            activation: Activation = Activation.NONE,
            interpret: bool = False) -> jnp.ndarray:
    """Weight-stationary GEMM, one pallas_call end to end.

    Weight-major grid (gn outermost), VMEM-resident accumulator across the K
    stream, and the rounding-shift/saturation/activation epilogue fused on
    the final K step. The int32 accumulator never exists in HBM at all: the
    only HBM write is the finished C at output precision (the seed lowered
    WS as acc-write + acc-re-read + epilogue-write across two pallas_calls).
    ``b`` and ``layer`` as for :func:`gemm_os`.
    """
    m, n, k = plan.m, plan.n, plan.k
    tm, tn, tk = plan.tile_m, plan.tile_n, plan.tile_k
    gm, gn, gk = plan.grid
    assert a.shape == (m, k), (a.shape, plan)
    bs, li = _weight_stack(b, layer, k, n)
    has_bias = d is not None
    if not has_bias:
        d = jnp.zeros((1, n), cfg.acc_jnp)  # placeholder operand (never read)

    kernel = functools.partial(
        _ws_kernel, nk=gk, acc_dtype=cfg.acc_jnp, out_dtype=cfg.output_jnp,
        shift=shift, activation=activation, has_bias=has_bias)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gn, gm, gk),  # weight-major: finish a B column strip, move on
        in_specs=[
            pl.BlockSpec((tk, tn),
                         lambda j, i, kk, li: (li[0] * gk + kk, j)),  # B
            pl.BlockSpec((tm, tk), lambda j, i, kk, li: (i, kk)),   # A
            pl.BlockSpec((tm if has_bias else 1, tn),
                         (lambda j, i, kk, li: (i, j)) if has_bias
                         else (lambda j, i, kk, li: (0, j))),       # D (bias)
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, li: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), cfg.acc_jnp)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), cfg.output_jnp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
            if cfg.pipeline_depth > 1 else ("arbitrary",) * 3),
        interpret=interpret,
    )(li, bs, a, d)


def _epilogue_kernel(acc_ref, c_ref, *, shift, activation, out_dtype):
    c_ref[...] = epi.apply(acc_ref[...], shift=shift, activation=activation,
                           out_dtype=out_dtype)


@kernel_contract("accumulator_epilogue")
def accumulator_epilogue(acc: jnp.ndarray, plan: TilePlan, cfg: GemminiConfig,
                         *, shift: int = 0,
                         activation: Activation = Activation.NONE,
                         interpret: bool = False) -> jnp.ndarray:
    """Scale/saturate/activate pass over the accumulator (mvout path)."""
    m, n = acc.shape
    tm, tn = plan.tile_m, plan.tile_n
    return pl.pallas_call(
        functools.partial(_epilogue_kernel, shift=shift, activation=activation,
                          out_dtype=cfg.output_jnp),
        grid=(m // tm, n // tn),
        in_specs=[pl.BlockSpec((tm, tn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), cfg.output_jnp),
        # every tile is independent: both axes pipeline freely (found by
        # lint GL503 — an undeclared grid serializes under Mosaic)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(acc)


def gemm(a, b, d, plan: TilePlan, cfg: GemminiConfig, *,
         dataflow: Optional[Dataflow] = None, layer=0, shift: int = 0,
         activation: Activation = Activation.NONE,
         interpret: bool = False) -> jnp.ndarray:
    """Dispatch on the elaborated (or runtime-selected) dataflow. ``b`` is
    a ``(K, N)`` weight or an ``(S, K, N)`` stack read at ``layer``."""
    df = dataflow or plan.dataflow
    if cfg.dataflow is not Dataflow.BOTH and df is not cfg.dataflow:
        raise ValueError(f"instance elaborated with {cfg.dataflow}, got {df}")
    fn = gemm_os if df is Dataflow.OS else gemm_ws
    return fn(a, b, d, plan, cfg, layer=layer, shift=shift,
              activation=activation, interpret=interpret)
