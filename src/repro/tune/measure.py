"""Wall-clock measurement harness for candidate schedules.

The one non-negotiable rule of timing dispatched JAX computations: sync
*inside* the loop. ``fn(*args)`` returns as soon as the work is enqueued, so
a loop that only syncs the last result measures dispatch overhead, not
execution (a bug the retired CPU kernel benchmark once had). ``time_callable`` calls
``jax.block_until_ready`` on every iteration and reports min-of-iters (the
noise-robust statistic schedulers should rank by) alongside the mean.

Backend selection for schedule measurement (all kernel classes):

* on a TPU host the candidate is lowered for real (``kernels.gemm`` /
  ``kernels.attention`` / ``kernels.conv`` with the candidate schedule) --
  the measured ranking is the true Mosaic ranking;
* on CPU hosts (CI) Mosaic cannot lower, so we time a *schedule proxy*: the
  XLA reference path on operands padded to the candidate schedule's dims.
  That captures the padding waste a bad blocking costs, but candidates that
  differ only in split time identically -- the tuner's analytic-cost
  tiebreaks (``tuner.analytic_cycles`` / ``schedules.attn_cycles`` /
  ``schedules.conv_cycles``) decide those, keeping CI deterministic.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.config import GemminiConfig
from repro.core.tiling import TilePlan


def time_callable(fn: Callable, *args, iters: int = 5,
                  warmup: int = 1, label: str = "") -> Dict[str, float]:
    """Time ``fn(*args)``: per-iteration sync, returns mean/min microseconds.

    With a process-global tracer installed (``repro.obs.trace.install``),
    each measurement lands as a ``measure:<label>`` span on the tuner
    track -- warmup/compile included, so trace timelines show what the
    tuner actually spent, not just the steady-state iterations.
    """
    from repro.obs import trace as otrace
    tracer = otrace.active()
    t_span = tracer.clock() if tracer is not None else 0.0
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))     # compile + warm caches
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    out = {"mean_us": sum(times) / len(times), "min_us": min(times),
           "iters": float(iters)}
    if tracer is not None:
        tracer.complete(f"measure:{label or 'anon'}", t_span,
                        tracer.clock(), cat="tune",
                        tid=otrace.TID_TUNER, min_us=out["min_us"],
                        mean_us=out["mean_us"], iters=iters)
    return out


def measurement_backend() -> str:
    """"pallas" when Mosaic can lower here, else the XLA schedule proxy."""
    return "pallas" if jax.default_backend() == "tpu" else "proxy"


def measure_plan(cfg: GemminiConfig, plan: TilePlan, *, has_bias: bool = False,
                 backend: Optional[str] = None, iters: int = 3,
                 warmup: int = 1) -> Dict[str, float]:
    """Wall-time one candidate plan on this host (zeros operands: timing is
    data-independent for dense GEMM)."""
    backend = backend or measurement_backend()
    a = jnp.zeros((plan.m, plan.k), cfg.input_jnp)
    b = jnp.zeros((plan.k, plan.n), cfg.input_jnp)
    d = jnp.zeros((plan.m, plan.n), cfg.acc_jnp) if has_bias else None

    if backend == "pallas":
        from repro.kernels import gemm as gemm_kernel

        def run(a, b):
            return gemm_kernel.gemm(a, b, d, plan, cfg,
                                    dataflow=plan.dataflow)
    else:
        from repro.kernels import ref as ref_ops

        def run(a, b):
            return ref_ops.gemm_ref(a, b, d, acc_dtype=cfg.acc_jnp,
                                    out_dtype=cfg.output_jnp)

    return time_callable(jax.jit(run), a, b, iters=iters, warmup=warmup,
                         label=f"gemm[{plan.m}x{plan.n}x{plan.k}"
                               f"/{plan.tile_m}x{plan.tile_n}x{plan.tile_k}]")


def measure_attn_schedule(cfg: GemminiConfig, sched, b: int, tq: int,
                          tk: int, h: int, kvh: int, d: int, *,
                          causal: bool = True, window: Optional[int] = None,
                          dtype="bf16", backend: Optional[str] = None,
                          iters: int = 3, warmup: int = 1) -> Dict[str, float]:
    """Wall-time one (block_q, block_k) candidate on this host.

    Pallas backend runs the real flash kernel with the candidate blocking;
    the CPU proxy times the XLA blockwise path on operands padded to the
    candidate's block grid (the padding waste a bad blocking costs).
    """
    from repro.tune.schedules import schedule_dtype
    backend = backend or measurement_backend()
    dt = schedule_dtype(dtype)
    eff = sched.effective(tq, tk)
    bq, bk = eff.block_q, eff.block_k

    if backend == "pallas":
        from repro.kernels import attention as attn_kernel
        q = jnp.zeros((b, tq, h, d), dt)
        k = jnp.zeros((b, tk, kvh, d), dt)
        v = jnp.zeros((b, tk, kvh, d), dt)

        def run(q, k, v):
            return attn_kernel.flash_attention(
                q, k, v, causal=causal, window=window,
                block_q=bq, block_k=bk)
    else:
        from repro.models.attention import blockwise_attention_xla
        nq, nk = -(-tq // bq), -(-tk // bk)
        q = jnp.zeros((b, nq * bq, h, d), dt)
        k = jnp.zeros((b, nk * bk, kvh, d), dt)
        v = jnp.zeros((b, nk * bk, kvh, d), dt)

        def run(q, k, v):
            return blockwise_attention_xla(q, k, v, causal=causal,
                                           window=window, block_k=bk)

    return time_callable(jax.jit(run), q, k, v, iters=iters, warmup=warmup,
                         label=f"attn[bq={bq},bk={bk}]")


def measure_paged_schedule(cfg: GemminiConfig, sched, b: int, h: int,
                           kvh: int, d: int, max_context: int, *,
                           window: Optional[int] = None, dtype="bf16",
                           backend: Optional[str] = None, iters: int = 3,
                           warmup: int = 1) -> Dict[str, float]:
    """Wall-time one page-size candidate for the paged decode kernel.

    Both backends build a pool sized for a full decode batch (every slot at
    ``max_context``, sequentially-allocated tables -- the layout cost of
    fragmentation is the allocator's concern, not the kernel's). Pallas
    runs the in-kernel-gather kernel; the CPU proxy times the explicit
    XLA gather path, which DOES see the page size (its gather/reshape
    granularity), so candidates genuinely measure differently even on CI.
    """
    from repro.core.context import ExecutionContext
    from repro.tune.schedules import schedule_dtype

    backend = backend or measurement_backend()
    dt = schedule_dtype(dtype)
    page = sched.effective(max_context).page_size
    mp = -(-max_context // page)
    n_pages = b * mp
    q = jnp.zeros((b, 1, h, d), dt)
    k_pool = jnp.zeros((1, kvh, n_pages + 1, page, d), dt)   # one layer
    v_pool = jnp.zeros((1, kvh, n_pages + 1, page, d), dt)
    tables = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
    lengths = jnp.full((b,), max_context, jnp.int32)
    ctx = ExecutionContext(
        cfg=cfg, backend="pallas" if backend == "pallas" else "xla",
        tune_mode="off")   # measuring: never recurse into the tuner

    def run(q, k_pool, v_pool):
        return ctx.paged_attention(q, k_pool, v_pool, tables, lengths, 0,
                                   window=window)

    return time_callable(jax.jit(run), q, k_pool, v_pool, iters=iters,
                         warmup=warmup, label=f"paged[page={page}]")


def measure_conv_schedule(cfg: GemminiConfig, sched, n: int, h: int, w: int,
                          ci: int, co: int, kh: int, kw: int, *,
                          stride: int = 1, padding: int = 0,
                          has_bias: bool = False,
                          backend: Optional[str] = None, iters: int = 3,
                          warmup: int = 1) -> Dict[str, float]:
    """Wall-time one co_tile candidate on this host.

    Pallas backend runs the implicit-im2col kernel with the candidate tile;
    the CPU proxy times the explicit-im2col reference with the output
    channels padded to the candidate's tile grid.
    """
    backend = backend or measurement_backend()
    ct = sched.effective(co).co_tile
    x = jnp.zeros((n, h, w, ci), cfg.input_jnp)
    bias = jnp.zeros((co,), cfg.acc_jnp) if has_bias else None

    if backend == "pallas":
        from repro.kernels import conv as conv_kernel
        wt = jnp.zeros((kh, kw, ci, co), cfg.input_jnp)

        def run(x, wt):
            return conv_kernel.conv2d_implicit(
                x, wt, bias, cfg=cfg, stride=stride, padding=padding,
                co_tile=ct)
    else:
        from repro.kernels import ref as ref_ops
        cop = -(-co // ct) * ct
        wt = jnp.zeros((kh, kw, ci, cop), cfg.input_jnp)
        bp = jnp.zeros((cop,), cfg.acc_jnp) if has_bias else None

        def run(x, wt):
            return ref_ops.conv2d_ref(x, wt, bp, stride=stride,
                                      padding=padding,
                                      acc_dtype=cfg.acc_jnp,
                                      out_dtype=cfg.output_jnp)

    return time_callable(jax.jit(run), x, wt, iters=iters, warmup=warmup,
                         label=f"conv[co_tile={ct}]")
