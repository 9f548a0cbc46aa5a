"""Declared contracts for every `pallas_call` in this package.

A :class:`KernelContract` is the statically checkable half of a Pallas
kernel: the grid, the dimension semantics, every operand's full shape /
block shape / index map / memory space, the VMEM scratch, how output
revisits reduce, and the dot-precision pairs the kernel body computes.
`repro.analysis.lint` abstractly interprets these over the tuner's
schedule lattice to prove coverage, write-race freedom, VMEM fit, and
precision soundness *before anything runs* (docs/analysis.md).

Contracts live next to the kernels (this package) so the declaration
and the launch site evolve together; the lint layer only consumes them.
Each launcher is annotated ``@kernel_contract("<name>")`` and the
builder with the same name constructs the contract for one concrete
(problem, schedule) instantiation — the builder mirrors the launcher's
`pallas_call` literally: same grid order, same lambdas, same scratch.

Index maps are the *same* lambda bodies as the launch sites, evaluated
by the linter on symbolic coordinates (`analysis/lint/affine.py`).
Operands whose real index map reads a scalar-prefetched ref (the paged
kernels' block-table gathers) cannot be affine — they declare
``data_dependent`` with the invariant the kernel maintains instead, and
the checker verifies everything else (block shape, VMEM, race, the
declared scalar-prefetch count) while skipping coverage for them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.config import GemminiConfig

# -- dtype normalization ----------------------------------------------------

_NAME_ALIASES = {
    "bf16": "bfloat16", "fp16": "float16", "fp32": "float32",
    "fp64": "float64",
}


def dt(dtype) -> Tuple[str, int]:
    """Any dtype spelling -> ("float"|"int", itemsize)."""
    if isinstance(dtype, tuple):
        return dtype
    if isinstance(dtype, str):
        dtype = _NAME_ALIASES.get(dtype, dtype)
        if dtype == "bfloat16":
            return ("float", 2)
    d = np.dtype(dtype)
    kind = "int" if d.kind in "iu" else "float"
    return (kind, d.itemsize)


# -- contract dataclasses ---------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """One input or output of a `pallas_call`.

    ``index_map`` takes the grid coordinates (same signature as the
    BlockSpec lambda, scalar-ref args dropped); ``data_dependent``
    (non-None) replaces it with a prose invariant when the real map
    gathers through prefetched scalars.  ``budget`` picks which VMEM
    budget the block charges when *resident* ("scratchpad" |
    "accumulator"), matching the tuner's per-kernel fit model.
    """

    name: str
    shape: Tuple[int, ...]
    block: Tuple[int, ...]
    index_map: Optional[Callable] = None
    dtype: Tuple[str, int] = ("float", 4)
    memory_space: str = "vmem"          # "vmem" | "smem"
    data_dependent: Optional[str] = None
    budget: str = "accumulator"


@dataclasses.dataclass(frozen=True)
class ScratchSpec:
    """One ``pltpu.VMEM`` scratch allocation."""

    name: str
    shape: Tuple[int, ...]
    dtype: Tuple[str, int] = ("float", 4)


@dataclasses.dataclass(frozen=True)
class Reduction:
    """How an output absorbs grid revisits along sequential axes.

    ``via="scratch"``: partials accumulate in the named VMEM scratch and
    flush to the output block on the final revisit — the only sound
    pattern for separated grid revisits.  ``via="alias"``: partials
    round-trip through an input/output alias in HBM — Pallas does NOT
    guarantee read-after-write through an alias across separated grid
    steps (the seed's silently-wrong WS GEMM), so the checker rejects
    it outright (GL203).
    """

    out: str
    axes: Tuple[str, ...]
    via: str = "scratch"                # "scratch" | "alias"
    scratch: Optional[str] = None
    alias_input: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class DotContract:
    """One matmul inside the kernel body: operand + accumulator dtypes."""

    lhs: Tuple[str, int]
    rhs: Tuple[str, int]
    acc: Tuple[str, int]


@dataclasses.dataclass(frozen=True)
class KernelContract:
    name: str
    grid: Tuple[Tuple[str, int], ...]          # (axis name, size), launch order
    semantics: Tuple[str, ...]                 # "parallel" | "arbitrary"
    inputs: Tuple[OperandSpec, ...]
    outputs: Tuple[OperandSpec, ...]
    scratch: Tuple[ScratchSpec, ...] = ()
    reductions: Tuple[Reduction, ...] = ()
    dots: Tuple[DotContract, ...] = ()
    scalar_prefetch: int = 0                   # PrefetchScalarGridSpec count
    io_aliases: Tuple[Tuple[int, int], ...] = ()   # input idx -> output idx

    def __post_init__(self):
        if len(self.semantics) != len(self.grid):
            raise ValueError(f"{self.name}: {len(self.semantics)} semantics "
                             f"for {len(self.grid)} grid axes")


# -- registry + launcher annotation ----------------------------------------

CONTRACT_BUILDERS: Dict[str, Callable[..., KernelContract]] = {}


def contract_builder(name: str):
    def deco(fn):
        CONTRACT_BUILDERS[name] = fn
        return fn
    return deco


def kernel_contract(name: str):
    """Annotate a `pallas_call` launcher with its contract name.

    Purely declarative (identity at runtime); the lint source pass
    requires every function containing a `pallas_call` to carry it and
    the name to resolve in :data:`CONTRACT_BUILDERS`.
    """
    def deco(fn):
        fn.__lint_contract__ = name
        return fn
    return deco


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# -- GEMM (kernels/gemm.py) -------------------------------------------------

def _gemm_common(cfg: GemminiConfig, plan, has_bias: bool):
    in_dt, acc_dt, out_dt = (dt(cfg.input_dtype), dt(cfg.acc_dtype),
                             dt(cfg.output_dtype))
    m, n, k = plan.m, plan.n, plan.k
    tm, tn, tk = plan.tile_m, plan.tile_n, plan.tile_k
    return in_dt, acc_dt, out_dt, m, n, k, tm, tn, tk


def _weight_layer(n_layers: int) -> int:
    """The layer the weight's index map is checked at. The GEMM prefetches
    its layer ``li`` in [0, n_layers) and reads block ``(li*gk + kk, j)``
    of the stack's ``(n_layers*K, N)`` view: an offset that only adds, so
    the deepest layer bounds every other."""
    return n_layers - 1


@contract_builder("gemm_os")
def gemm_os_contract(cfg: GemminiConfig, plan, *, has_bias: bool = False,
                     n_layers: int = 1) -> KernelContract:
    in_dt, acc_dt, out_dt, m, n, k, tm, tn, tk = \
        _gemm_common(cfg, plan, has_bias)
    gm, gn, gk = plan.grid
    li = _weight_layer(n_layers)
    semantics = (("arbitrary",) * 3 if cfg.pipeline_depth == 1
                 else ("parallel", "parallel", "arbitrary"))
    d_spec = OperandSpec(
        "d", (m if has_bias else 1, n), (tm if has_bias else 1, tn),
        (lambda i, j, kk: (i, j)) if has_bias
        else (lambda i, j, kk: (0, j)),
        acc_dt, budget="scratchpad")
    return KernelContract(
        name="gemm_os",
        grid=(("i", gm), ("j", gn), ("kk", gk)),
        semantics=semantics,
        scalar_prefetch=1,                         # the weight's layer
        inputs=(
            OperandSpec("a", (m, k), (tm, tk),
                        lambda i, j, kk: (i, kk), in_dt,
                        budget="scratchpad"),
            OperandSpec("b", (n_layers * k, n), (tk, tn),
                        lambda i, j, kk: (li * gk + kk, j), in_dt,
                        budget="scratchpad"),
            d_spec,
        ),
        outputs=(OperandSpec("c", (m, n), (tm, tn),
                             lambda i, j, kk: (i, j), out_dt),),
        scratch=(ScratchSpec("acc", (tm, tn), acc_dt),),
        reductions=(Reduction("c", ("kk",), via="scratch", scratch="acc"),),
        dots=(DotContract(in_dt, in_dt, acc_dt),),
    )


@contract_builder("gemm_ws")
def gemm_ws_contract(cfg: GemminiConfig, plan, *, has_bias: bool = False,
                     n_layers: int = 1) -> KernelContract:
    in_dt, acc_dt, out_dt, m, n, k, tm, tn, tk = \
        _gemm_common(cfg, plan, has_bias)
    gm, gn, gk = plan.grid
    li = _weight_layer(n_layers)
    d_spec = OperandSpec(
        "d", (m if has_bias else 1, n), (tm if has_bias else 1, tn),
        (lambda j, i, kk: (i, j)) if has_bias
        else (lambda j, i, kk: (0, j)),
        acc_dt, budget="scratchpad")
    return KernelContract(
        name="gemm_ws",
        grid=(("j", gn), ("i", gm), ("kk", gk)),   # weight-major
        semantics=("parallel", "parallel", "arbitrary"),
        scalar_prefetch=1,                         # the weight's layer
        inputs=(
            OperandSpec("b", (n_layers * k, n), (tk, tn),
                        lambda j, i, kk: (li * gk + kk, j), in_dt,
                        budget="scratchpad"),
            OperandSpec("a", (m, k), (tm, tk),
                        lambda j, i, kk: (i, kk), in_dt,
                        budget="scratchpad"),
            d_spec,
        ),
        outputs=(OperandSpec("c", (m, n), (tm, tn),
                             lambda j, i, kk: (i, j), out_dt),),
        scratch=(ScratchSpec("acc", (tm, tn), acc_dt),),
        reductions=(Reduction("c", ("kk",), via="scratch", scratch="acc"),),
        dots=(DotContract(in_dt, in_dt, acc_dt),),
    )


@contract_builder("accumulator_epilogue")
def accumulator_epilogue_contract(cfg: GemminiConfig, plan, *,
                                  m: int, n: int) -> KernelContract:
    acc_dt, out_dt = dt(cfg.acc_dtype), dt(cfg.output_dtype)
    tm, tn = plan.tile_m, plan.tile_n
    return KernelContract(
        name="accumulator_epilogue",
        grid=(("i", m // tm), ("j", n // tn)),
        semantics=("parallel", "parallel"),
        inputs=(OperandSpec("acc", (m, n), (tm, tn),
                            lambda i, j: (i, j), acc_dt,
                            budget="scratchpad"),),
        outputs=(OperandSpec("c", (m, n), (tm, tn),
                             lambda i, j: (i, j), out_dt),),
    )


# -- attention (kernels/attention.py) ---------------------------------------

def _attn_dt(dtype) -> Tuple[str, int]:
    return dt(dtype)


@contract_builder("flash_attention")
def flash_attention_contract(cfg: GemminiConfig, *, b: int, h: int, kvh: int,
                             tq: int, tk: int, d: int, block_q: int,
                             block_k: int, dtype="bf16") -> KernelContract:
    io = _attn_dt(dtype)
    f32 = ("float", 4)
    block_q = min(block_q, max(tq, 8))
    block_k = min(block_k, max(tk, 8))
    nq, nk = _cdiv(tq, block_q), _cdiv(tk, block_k)
    rep = h // kvh
    kv_shape = (b, kvh, nk * block_k, d)
    kv_map = lambda bb, hh, i, j: (bb, hh // rep, j, 0)   # noqa: E731
    return KernelContract(
        name="flash_attention",
        grid=(("bb", b), ("hh", h), ("i", nq), ("j", nk)),
        semantics=("parallel", "parallel", "parallel", "arbitrary"),
        inputs=(
            OperandSpec("q", (b, h, nq * block_q, d), (1, 1, block_q, d),
                        lambda bb, hh, i, j: (bb, hh, i, 0), io),
            OperandSpec("k", kv_shape, (1, 1, block_k, d), kv_map, io,
                        budget="scratchpad"),
            OperandSpec("v", kv_shape, (1, 1, block_k, d), kv_map, io,
                        budget="scratchpad"),
        ),
        outputs=(OperandSpec("o", (b, h, nq * block_q, d),
                             (1, 1, block_q, d),
                             lambda bb, hh, i, j: (bb, hh, i, 0), io),),
        scratch=(ScratchSpec("m", (block_q,), f32),
                 ScratchSpec("l", (block_q,), f32),
                 ScratchSpec("acc", (block_q, d), f32)),
        reductions=(Reduction("o", ("j",), via="scratch", scratch="acc"),),
        dots=(DotContract(io, io, f32),),
    )


@contract_builder("decode_attention")
def decode_attention_contract(cfg: GemminiConfig, *, b: int, h: int,
                              kvh: int, s: int, d: int, block_k: int,
                              dtype="bf16") -> KernelContract:
    io = _attn_dt(dtype)
    f32 = ("float", 4)
    rep = h // kvh
    block_k = min(block_k, s)
    nk = _cdiv(s, block_k)
    kv_shape = (b * kvh, nk * block_k, d)
    return KernelContract(
        name="decode_attention",
        grid=(("g", b * kvh), ("j", nk)),
        semantics=("parallel", "arbitrary"),
        inputs=(
            OperandSpec("q", (b * kvh, rep, d), (1, rep, d),
                        lambda g, j: (g, 0, 0), io),
            OperandSpec("k", kv_shape, (1, block_k, d),
                        lambda g, j: (g, j, 0), io, budget="scratchpad"),
            OperandSpec("v", kv_shape, (1, block_k, d),
                        lambda g, j: (g, j, 0), io, budget="scratchpad"),
            OperandSpec("lens", (b * kvh,), (1,),
                        lambda g, j: (g,), ("int", 4), memory_space="smem"),
        ),
        outputs=(OperandSpec("o", (b * kvh, rep, d), (1, rep, d),
                             lambda g, j: (g, 0, 0), io),),
        scratch=(ScratchSpec("m", (rep,), f32),
                 ScratchSpec("l", (rep,), f32),
                 ScratchSpec("acc", (rep, d), f32)),
        reductions=(Reduction("o", ("j",), via="scratch", scratch="acc"),),
        dots=(DotContract(io, io, f32),),
    )


_PAGED_GATHER = ("K/V page index gathers through the scalar-prefetched "
                 "block table, in the rows of the prefetched layer; dead "
                 "steps clamp to the last live page so the read never "
                 "leaves [0, n_pages)")


def paged_heads_per_block(cfg: GemminiConfig, *, kvh: int, rep: int,
                          page: int, d: int, itemsize: int) -> int:
    """KV heads one paged-decode grid step takes: all ``kvh`` where the
    double-buffered K and V blocks fit the scratchpad and the resident
    query, output and softmax scratch fit the accumulator; else the
    largest divisor of ``kvh`` that fits; 1 where none does (the lint
    then reports the overflow)."""
    for hb in range(kvh, 0, -1):
        if kvh % hb:
            continue
        streamed = cfg.pipeline_depth * 2 * hb * page * d * itemsize
        resident = hb * rep * (d * (2 * itemsize + 4) + 2 * 4)
        if (streamed <= cfg.scratchpad_bytes
                and resident <= cfg.accumulator_bytes):
            return hb
    return 1


@contract_builder("paged_decode_attention")
def paged_decode_attention_contract(cfg: GemminiConfig, *, b: int, h: int,
                                    kvh: int, d: int, page: int, mp: int,
                                    n_pages: int, n_layers: int = 1,
                                    dtype="bf16") -> KernelContract:
    io = _attn_dt(dtype)
    f32 = ("float", 4)
    rep = h // kvh
    hb = paged_heads_per_block(cfg, kvh=kvh, rep=rep, page=page, d=d,
                               itemsize=io[1])
    pool = (n_layers * kvh, n_pages, page, d)      # the stack's free view
    if hb == kvh:
        grid = (("bb", b), ("j", mp))
        head_map = lambda bb, j: (bb, 0, 0, 0)              # noqa: E731
    else:
        grid = (("bb", b), ("g", kvh // hb), ("j", mp))
        head_map = lambda bb, g, j: (bb, g, 0, 0)           # noqa: E731
    return KernelContract(
        name="paged_decode_attention",
        grid=grid,
        semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",),
        scalar_prefetch=2,
        inputs=(
            OperandSpec("q", (b, kvh, rep, d), (1, hb, rep, d), head_map,
                        io),
            OperandSpec("k_pool", pool, (hb, 1, page, d), None, io,
                        data_dependent=_PAGED_GATHER, budget="scratchpad"),
            OperandSpec("v_pool", pool, (hb, 1, page, d), None, io,
                        data_dependent=_PAGED_GATHER, budget="scratchpad"),
        ),
        outputs=(OperandSpec("o", (b, kvh, rep, d), (1, hb, rep, d),
                             head_map, io),),
        scratch=(ScratchSpec("m", (hb, rep), f32),
                 ScratchSpec("l", (hb, rep), f32),
                 ScratchSpec("acc", (hb, rep, d), f32)),
        reductions=(Reduction("o", ("j",), via="scratch", scratch="acc"),),
        dots=(DotContract(io, io, f32),          # q.K^T, stored operands
              DotContract(f32, f32, f32)),       # P.V
    )


@contract_builder("paged_prefill_attention")
def paged_prefill_attention_contract(cfg: GemminiConfig, *, h: int, kvh: int,
                                     tq: int, d: int, page: int, mp: int,
                                     n_pages: int, block_q: int,
                                     n_layers: int = 1,
                                     dtype="bf16") -> KernelContract:
    io = _attn_dt(dtype)
    f32 = ("float", 4)
    block_q = min(block_q, max(tq, 8))
    nq = _cdiv(tq, block_q)
    pool = (n_layers * kvh, n_pages, page, d)      # the stack's free view
    return KernelContract(
        name="paged_prefill_attention",
        grid=(("hh", h), ("i", nq), ("j", mp)),
        semantics=("parallel", "parallel", "arbitrary"),
        scalar_prefetch=2,
        inputs=(
            OperandSpec("q", (h, nq * block_q, d), (1, block_q, d),
                        lambda hh, i, j: (hh, i, 0), io),
            OperandSpec("k_pool", pool, (1, 1, page, d), None, io,
                        data_dependent=_PAGED_GATHER, budget="scratchpad"),
            OperandSpec("v_pool", pool, (1, 1, page, d), None, io,
                        data_dependent=_PAGED_GATHER, budget="scratchpad"),
        ),
        outputs=(OperandSpec("o", (h, nq * block_q, d), (1, block_q, d),
                             lambda hh, i, j: (hh, i, 0), io),),
        scratch=(ScratchSpec("m", (block_q,), f32),
                 ScratchSpec("l", (block_q,), f32),
                 ScratchSpec("acc", (block_q, d), f32)),
        reductions=(Reduction("o", ("j",), via="scratch", scratch="acc"),),
        dots=(DotContract(io, io, f32),),
    )


# -- conv (kernels/conv.py) -------------------------------------------------

@contract_builder("conv2d_implicit")
def conv2d_implicit_contract(cfg: GemminiConfig, *, n: int, h: int, w: int,
                             ci: int, co: int, kh: int, kw: int,
                             co_tile: int, stride: int = 1, padding: int = 0,
                             has_bias: bool = False) -> KernelContract:
    in_dt, acc_dt, out_dt = (dt(cfg.input_dtype), dt(cfg.acc_dtype),
                             dt(cfg.output_dtype))
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    hp, wp = (oh - 1) * stride + kh, (ow - 1) * stride + kw
    co_tile = min(co_tile, co)
    nco = _cdiv(co, co_tile)
    inputs = [
        # whole padded input block resident across the tap stream: charged
        # to the scratchpad budget exactly as schedules._conv_fits does.
        OperandSpec("x", (n, hp, wp, ci), (1, hp, wp, ci),
                    lambda nn, cc, tt: (nn, 0, 0, 0), in_dt,
                    budget="scratchpad"),
        OperandSpec("w", (kh * kw, ci, nco * co_tile), (1, ci, co_tile),
                    lambda nn, cc, tt: (tt, 0, cc), in_dt,
                    budget="scratchpad"),
    ]
    if has_bias:
        inputs.append(OperandSpec("bias", (1, nco * co_tile), (1, co_tile),
                                  lambda nn, cc, tt: (0, cc), acc_dt,
                                  budget="scratchpad"))
    return KernelContract(
        name="conv2d_implicit",
        grid=(("nn", n), ("cc", nco), ("tt", kh * kw)),
        semantics=("parallel", "parallel", "arbitrary"),
        inputs=tuple(inputs),
        outputs=(OperandSpec("y", (n, oh, ow, nco * co_tile),
                             (1, oh, ow, co_tile),
                             lambda nn, cc, tt: (nn, 0, 0, cc), out_dt),),
        scratch=(ScratchSpec("acc", (oh * ow, co_tile), acc_dt),),
        reductions=(Reduction("y", ("tt",), via="scratch", scratch="acc"),),
        dots=(DotContract(in_dt, in_dt, acc_dt),),
    )


# -- Mamba-2 SSD (kernels/mamba2.py) ----------------------------------------

@contract_builder("ssd")
def ssd_contract(cfg: GemminiConfig, *, bsz: int, h: int, nc: int, q: int,
                 p: int, n: int, ngroups: int, dtype="bf16",
                 return_final_state: bool = False) -> KernelContract:
    io = dt(dtype)
    f32 = ("float", 4)
    hpg = h // ngroups
    bc_map = lambda bb, hh, cc: (bb, hh // hpg, cc, 0, 0)   # noqa: E731
    outputs = [OperandSpec("y", (bsz, h, nc, q, p), (1, 1, 1, q, p),
                           lambda bb, hh, cc: (bb, hh, cc, 0, 0), io)]
    reductions = []
    if return_final_state:
        outputs.append(OperandSpec(
            "fs", (bsz, h, n, p), (1, 1, n, p),
            lambda bb, hh, cc: (bb, hh, 0, 0), f32))
        reductions.append(Reduction("fs", ("cc",), via="scratch",
                                    scratch="state"))
    return KernelContract(
        name="ssd",
        grid=(("bb", bsz), ("hh", h), ("cc", nc)),
        semantics=("parallel", "parallel", "arbitrary"),
        inputs=(
            OperandSpec("x", (bsz, h, nc, q, p), (1, 1, 1, q, p),
                        lambda bb, hh, cc: (bb, hh, cc, 0, 0), io,
                        budget="scratchpad"),
            OperandSpec("dt", (bsz, h, nc, q), (1, 1, 1, q),
                        lambda bb, hh, cc: (bb, hh, cc, 0), io,
                        budget="scratchpad"),
            OperandSpec("a", (h,), (1,), lambda bb, hh, cc: (hh,),
                        ("float", 4), memory_space="smem"),
            OperandSpec("d", (h,), (1,), lambda bb, hh, cc: (hh,),
                        ("float", 4), memory_space="smem"),
            OperandSpec("b", (bsz, ngroups, nc, q, n), (1, 1, 1, q, n),
                        bc_map, io, budget="scratchpad"),
            OperandSpec("c", (bsz, ngroups, nc, q, n), (1, 1, 1, q, n),
                        bc_map, io, budget="scratchpad"),
        ),
        outputs=tuple(outputs),
        scratch=(ScratchSpec("state", (n, p), f32),),
        reductions=tuple(reductions),
        dots=(DotContract(io, io, f32),),
    )
