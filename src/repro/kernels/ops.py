"""Kernel-layer op implementations behind :class:`ExecutionContext`.

The canonical dispatch API is ``repro.core.context.ExecutionContext``:
callers hold one context value (cfg + backend + tune policy + optional
mesh) and launch ``ctx.gemm(...)``, ``ctx.flash_attention(...)``, ....
The ``*_impl`` functions here are the kernel-layer entries that registry
dispatches to; they own shape legalization (zero-padding to the elaborated
array dimension, exactly as the paper's library zero-pads operands,
section 3.3), unpadding of results, and flag-gated schedule resolution.

Backends (one per context, no longer per call):

* ``"pallas"``    -- real TPU lowering (Mosaic). Target deployment path.
* ``"interpret"`` -- pl.pallas_call(interpret=True): executes the kernel body
                     in Python on CPU. Used by all kernel tests in this repo.
* ``"xla"``       -- pure-jnp path (the ref oracle numerics) that XLA can
                     SPMD-partition; used by the 512-device multi-pod dry-run,
                     where Mosaic kernels cannot lower on the CPU backend.

Under ``ctx.mesh`` the context wraps these impls in ``shard_map``, so the
shapes they see -- and the schedules ``_resolve_plan`` /
``_resolve_attn_blocks`` fingerprint -- are PER-DEVICE shapes (what
``tune.warm_model_plans(n_shards=...)`` warms), not the global logical
shapes GSPMD would otherwise trace them with.

The PR-5 ``ops.gemm(..., backend=...)`` deprecation shims were removed in
PR 7 after their one-release grace period; lint rule GL506 forbids binding
any legacy top-level alias in this module again.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.config import Activation, Dataflow, GemminiConfig
from repro.core.tiling import TilePlan, plan_gemm
from repro.kernels import gemm as gemm_kernel
from repro.kernels import ref as ref_ops

Backend = str  # "pallas" | "interpret" | "xla"


def _pad2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr < 0 or pc < 0:
        raise ValueError(f"operand {x.shape} exceeds its plan dims "
                         f"({rows}, {cols}); plan solved for a smaller GEMM?")
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def _resolve_plan(cfg: GemminiConfig, m: int, n: int, k: int, *,
                  dataflow: Optional[Dataflow], has_bias: bool) -> TilePlan:
    """Plan for this GEMM, honoring the effective tune mode (the process
    ``GEMMINI_TUNE`` flag, or the dispatching context's ``tune_mode``
    override scoped around this call).

    ``tune_mode=off`` keeps the greedy analytic solver on the hot path with
    no tuner import at all; otherwise the tuner consults (and under ``full``
    populates) the persistent plan cache. Inside a mesh'd context this runs
    under ``shard_map`` tracing, so ``m`` is the PER-DEVICE row count.
    """
    from repro.core import flags
    if flags.get("tune_mode") == "off":
        return plan_gemm(cfg, m, n, k, dataflow=dataflow, has_bias=has_bias)
    from repro.tune import tuner
    return tuner.resolve_plan(cfg, m, n, k, dataflow=dataflow,
                              has_bias=has_bias)


def gemm_impl(a: jnp.ndarray, b: jnp.ndarray, d: Optional[jnp.ndarray] = None,
              *, cfg: GemminiConfig, plan: Optional[TilePlan] = None,
              dataflow: Optional[Dataflow] = None, shift: int = 0,
              activation: Activation = Activation.NONE,
              backend: Backend = "xla") -> jnp.ndarray:
    """C = act(round_shift(A @ B + D)) on the elaborated instance.

    a: (M, K), b: (K, N), d: broadcastable (1|M, N) bias at acc dtype.
    Reached as ``ctx.gemm(a, b, d, ...)``; the context supplies ``cfg``
    and ``backend`` and (under a mesh) shards M.

    backend x tune-mode matrix (``plan`` given short-circuits both):

    ==========  ===========================================================
    backend     tune_mode=off            tune_mode=cached / full
    ==========  ===========================================================
    xla         ``ref.gemm_ref``: plain XLA dot with the fused
                accumulate/shift/saturate/activation epilogue. Plan-free
                (no tiling), so the tune flag never enters -- this is the
                SPMD-partitionable reference the dry-run lowers (GSPMD,
                not shard_map, partitions it; ``ctx.mesh`` is ignored).
    pallas /    greedy analytic            persistent plan cache keyed by
    interpret   ``plan_gemm`` solve,       the GEMM fingerprint; ``full``
                no tuner import on         measures and populates misses,
                the hot path               ``cached`` degrades misses to
                                           the analytic solve.
                Under ``ctx.mesh`` both columns resolve at the PER-DEVICE
                M (the shard_map-local shape).
    ==========  ===========================================================
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")
    if backend == "xla":
        return ref_ops.gemm_ref(a, b, d, acc_dtype=cfg.acc_jnp,
                                out_dtype=cfg.output_jnp, shift=shift,
                                activation=activation)

    def run(a, b, d, plan=None):
        m, k = a.shape
        n = b.shape[1]
        plan = plan or _resolve_plan(cfg, m, n, k, dataflow=dataflow,
                                     has_bias=d is not None)
        ap = _pad2(a, plan.m, plan.k)
        bp = _pad2(b, plan.k, plan.n)
        dp = None
        if d is not None:
            dp = _pad2(jnp.broadcast_to(d, (m, n)).astype(cfg.acc_jnp),
                       plan.m, plan.n)
        out = gemm_kernel.gemm(ap, bp, dp, plan, cfg, dataflow=dataflow,
                               shift=shift, activation=activation,
                               interpret=(backend == "interpret"))
        return out[:m, :n]

    if shift == 0 and activation is Activation.NONE and \
            jnp.issubdtype(cfg.input_jnp, jnp.floating):
        return _linear_gemm(run, a, b, d, plan)
    return run(a, b, d, plan)


def _linear_gemm(run, a, b, d, plan):
    """``run(a, b, d)`` (the float engine datapath, linear in each operand)
    with a VJP built from the same kernel: pallas_call itself has no
    transpose, and the trainer differentiates every projection.

      dA = dC @ B^T    dB = A^T @ dC    dD = dC summed over D's broadcast
    """

    @jax.custom_vjp
    def f(a, b, d):
        return run(a, b, d, plan)

    def fwd(a, b, d):
        return run(a, b, d, plan), (a, b, d)

    def bwd(res, dc):
        a, b, d = res
        da = run(dc, b.T, None).astype(a.dtype)
        db = run(a.T, dc, None).astype(b.dtype)
        dd = None
        if d is not None:
            dd = dc if d.size != dc.shape[1] else \
                jnp.sum(dc.astype(jnp.float32), axis=0).reshape(d.shape)
            dd = dd.astype(d.dtype)
        return da, db, dd

    f.defvjp(fwd, bwd)
    return f(a, b, d)


def matmul_impl(a: jnp.ndarray, b: jnp.ndarray, *, cfg: GemminiConfig,
                backend: Backend = "xla", **kw) -> jnp.ndarray:
    """Batched-LHS matmul: a may be (..., K); collapsed to 2D for the
    engine. Pure shape sugar over :func:`gemm_impl` -- backend and
    tune-mode behavior are exactly gemm's matrix with
    M = prod(leading dims)."""
    lead = a.shape[:-1]
    y = gemm_impl(a.reshape(-1, a.shape[-1]), b, cfg=cfg, backend=backend,
                  **kw)
    return y.reshape(*lead, b.shape[-1])


# -- conv2d -------------------------------------------------------------------
def _resolve_conv_co_tile(cfg: GemminiConfig, x, w, *, has_bias: bool,
                          stride: int, padding: int) -> int:
    """co_tile for this conv, honoring the effective tune mode (the conv
    twin of ``_resolve_plan``): ``off`` keeps the kernel's static default
    with no tuner import; otherwise the tuner consults the persistent
    cache."""
    from repro.core import flags
    if flags.get("tune_mode") == "off":
        # schedules is import-light (no measurement machinery): off mode
        # still never touches the tuner/cache.
        from repro.tune.schedules import DEFAULT_CO_TILE
        return DEFAULT_CO_TILE
    from repro.tune import tuner
    n, h, wd, ci = x.shape
    kh, kw, _, co = w.shape
    return tuner.resolve_conv_schedule(
        cfg, n, h, wd, ci, co, kh, kw, stride=stride, padding=padding,
        has_bias=has_bias).co_tile


def conv2d_impl(x, w, b=None, *, cfg: GemminiConfig, stride: int = 1,
                padding: int = 0, shift: int = 0,
                activation: Activation = Activation.NONE,
                backend: Backend = "xla", fused: bool = False,
                co_tile: Optional[int] = None):
    """Conv2D on the GEMM engine. Reached as ``ctx.conv2d(x, w, b, ...)``;
    under a mesh the image batch N is sharded.

    backend x fused matrix:

    ==========  ===========================================================
    backend     fused=False              fused=True
    ==========  ===========================================================
    xla         ``ref.conv2d_ref``: explicit im2col + XLA GEMM with the
                fused accumulate/shift/saturate/activation epilogue. This
                IS the fused-equivalent reference -- bit-identical to the
                fused kernel -- so ``fused`` does not change the xla path.
    pallas /    host im2col +            implicit-im2col Pallas kernel
    interpret   engine GEMM (the         (paper section 7 future work;
                paper's shipped          kernels/conv.py), ``co_tile``
                design)                  resolved via ``repro.tune`` when
                                         tuning is enabled
    ==========  ===========================================================

    ``co_tile``: explicit output-channel tile for the fused kernel;
    ``None`` resolves it through the flag-gated tuner (static default 128
    under ``tune_mode=off``).
    """
    if backend == "xla":
        # fused=True intentionally routes here too (there is no separate
        # XLA lowering): conv2d_ref is the fused-equivalent reference, not
        # a silent fallback -- see the matrix above.
        return ref_ops.conv2d_ref(x, w, b, stride=stride, padding=padding,
                                  acc_dtype=cfg.acc_jnp,
                                  out_dtype=cfg.output_jnp, shift=shift,
                                  activation=activation)
    if fused:
        from repro.kernels import conv as conv_kernel
        if co_tile is None:
            co_tile = _resolve_conv_co_tile(cfg, x, w, has_bias=b is not None,
                                            stride=stride, padding=padding)
        return conv_kernel.conv2d_implicit(
            x, w, b, cfg=cfg, stride=stride, padding=padding, shift=shift,
            activation=activation, co_tile=co_tile,
            interpret=(backend == "interpret"))
    n, h, wd, c = x.shape
    kh, kw, _, co = w.shape
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (wd + 2 * padding - kw) // stride + 1
    a = ref_ops.im2col(x, kh, kw, stride, padding)   # host-side im2col
    y = gemm_impl(a, w.reshape(-1, co), None if b is None else b[None, :],
                  cfg=cfg, shift=shift, activation=activation,
                  backend=backend)
    return y.reshape(n, oh, ow, co)


# -- attention ---------------------------------------------------------------
# Engine config the attention tuner falls back to when the caller has none:
# attention streams bf16 and accumulates f32 regardless of the GEMM engine's
# quantized datapath, so only the VMEM budgets / dim are consulted.
_ATTN_ENGINE_CFG: Optional[GemminiConfig] = None


def _attn_engine_cfg() -> GemminiConfig:
    global _ATTN_ENGINE_CFG
    if _ATTN_ENGINE_CFG is None:
        _ATTN_ENGINE_CFG = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                                         output_dtype="bf16")
    return _ATTN_ENGINE_CFG


def _resolve_attn_blocks(cfg: Optional[GemminiConfig], q, k, *, causal: bool,
                         window: Optional[int]) -> "tuple[int, int]":
    """(block_q, block_k) for this attention, honoring the effective tune
    mode (the attention twin of ``_resolve_plan``). Inside a mesh'd
    context this runs under ``shard_map`` tracing, so the fingerprinted
    batch is the PER-DEVICE batch."""
    from repro.core import flags
    if flags.get("tune_mode") == "off":
        from repro.tune.schedules import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    from repro.tune import tuner
    b, tq, h, d = q.shape
    _, tk, kvh, _ = k.shape
    sched = tuner.resolve_attn_schedule(
        cfg or _attn_engine_cfg(), b, tq, tk, h, kvh, d, causal=causal,
        window=window, dtype=q.dtype)
    return sched.block_q, sched.block_k


def flash_attention_impl(q, k, v, *, causal: bool = True,
                         window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         cfg: Optional[GemminiConfig] = None,
                         backend: Backend = "xla"):
    """Blockwise-softmax attention. See kernels/attention.py for the TPU
    kernel. Reached as ``ctx.flash_attention(q, k, v, ...)``; under a
    mesh the batch B is sharded.

    ``block_q``/``block_k``: explicit blocking for the Pallas kernel;
    ``None`` resolves the schedule through the flag-gated tuner (static
    512/512 defaults under ``tune_mode=off``). ``cfg`` supplies the VMEM
    budgets for schedule legality/fingerprinting (a bf16 engine default is
    used when omitted -- the value every launcher elaborates with, so
    context-supplied and defaulted cfgs fingerprint identically today).

    backend x tune-mode matrix:

    ==========  ===========================================================
    xla         ``blockwise_attention_xla``: online-softmax scan over
                1024-key blocks (clamped to a 128-multiple of Tk), exact
                oracle numerics, differentiable (the train path), ignores
                block_q/block_k/cfg and the tune mode entirely.
    pallas /    off: static 512/512        cached/full: ``AttnSchedule``
    interpret   blocks                     (block_q, block_k) from the
                                           schema-v2 plan cache, measured
                                           under ``full``; fingerprinted
                                           at the per-device batch when
                                           the context carries a mesh
    ==========  ===========================================================

    A *traced* window (gemma-style mixed local:global layers scanned as
    data) cannot parameterize a Mosaic kernel; callers route those to an
    xla-backend context (see ``models.attention._route_window``).
    """
    if backend == "xla":
        from repro.models.attention import blockwise_attention_xla
        return blockwise_attention_xla(q, k, v, causal=causal, window=window,
                                       softcap=softcap, scale=scale)
    if block_q is None or block_k is None:
        bq, bk = _resolve_attn_blocks(cfg, q, k, causal=causal, window=window)
        block_q = block_q if block_q is not None else bq
        block_k = block_k if block_k is not None else bk
    from repro.kernels import attention as attn_kernel
    return attn_kernel.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=(backend == "interpret"))


# -- paged attention ---------------------------------------------------------
def paged_attention_impl(q, k_pool, v_pool, block_tables, lengths, layer, *,
                         window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None,
                         cfg: Optional[GemminiConfig] = None,
                         backend: Backend = "xla"):
    """Single-token decode over a paged KV cache (the serving engine's hot
    loop). q: (B, 1, H, D); k_pool/v_pool: (L, KVH, NP, page, D), every
    layer's pools stacked, read in place; layer: scalar int32 (may be
    traced), the layer attended; block_tables: (B, MP) int32; lengths: (B,)
    int32 live tokens incl. the current one. Reached as
    ``ctx.paged_attention(...)``; under a mesh the decode slots (B) are
    sharded against replicated pools.

    The *page size* is the tuned schedule here -- it is baked into the pool
    shape when the serving engine sizes its cache arena through
    ``repro.tune.resolve_paged_attn_schedule``, not resolved per call (a
    pool cannot be re-blocked mid-flight).

    backend matrix:

    ==========  ===========================================================
    xla         ``paged_decode_attention_xla``: explicit block-table
                gather, bit-identical to the dense ``decode_attention``
                (the engine's exact-match contract); SPMD-partitionable.
    pallas /    ``kernels/attention.paged_decode_attention``: block tables
    interpret   scalar-prefetched to SMEM, one pool page of every KV head
                (or of as many as ``cfg``'s scratchpad holds) DMA'd per
                grid step via the BlockSpec index map; dead pages
                clamp-elided and compute-skipped (``block_live``).
    ==========  ===========================================================
    """
    if backend == "xla":
        from repro.models.attention import (PagedKVCache,
                                            paged_decode_attention_xla)
        cache = PagedKVCache(k_pool, v_pool, block_tables, lengths,
                             k_pool.shape[3], layer)
        return paged_decode_attention_xla(q, cache, window=window,
                                          softcap=softcap, scale=scale)
    from repro.kernels import attention as attn_kernel
    return attn_kernel.paged_decode_attention(
        q, k_pool, v_pool, block_tables, lengths, layer, window=window,
        softcap=softcap, scale=scale, cfg=cfg,
        interpret=(backend == "interpret"))


def paged_prefill_attention_impl(q, k_pool, v_pool, block_table, start,
                                 layer, *,
                                 window: Optional[int] = None,
                                 softcap: Optional[float] = None,
                                 scale: Optional[float] = None,
                                 kv_pages: Optional[int] = None,
                                 backend: Backend = "xla"):
    """Chunked-prefill attention over a paged KV cache: one request's fresh
    chunk of queries (q: (1, T, H, D), logical positions [start, start+T))
    attends cache pages + the chunk itself, all through the request's block
    table (``block_table``: (MP,) int32) into layer ``layer`` of the
    stacked pools (``k_pool``/``v_pool``: (L, KVH, NP, page, D), read in
    place). The chunk's own KV must already be scattered into the pools
    (write first, then attend -- the decode discipline); ``start`` may be
    a traced scalar, so one compile bucket serves every chunk offset of a
    given chunk length. Reached as
    ``ctx.paged_prefill_attention(...)``; per-request (B == 1), so a mesh
    never shards it.

    ``kv_pages``: STATIC upper bound on the table prefix that can hold
    live keys -- the admission-time bound the serving engine derives from
    the request's full (padded) prompt length. The table is sliced to its
    first ``kv_pages`` entries before either backend runs, so the xla
    gather twin contracts ``kv_pages * page`` keys instead of the full
    table capacity ``MP * page`` (dead-key MACs cut for short prompts on
    long-context engines) and the kernel grid walks ``kv_pages`` logical
    pages. The caller must guarantee ``kv_pages * page >= start + T`` for
    every chunk of the request (the engine uses the whole-prompt padded
    footprint, which bounds every chunk frontier). ``None`` keeps the full
    table.

    backend matrix (no tunable flags enter here; the page size was baked
    into the pool shape at engine startup, see :func:`paged_attention_impl`):

    ==========  ===========================================================
    xla         explicit gather + ``blockwise_attention_xla`` with the same
                KV blocking anchored at position 0 as the single-pass
                prefill path -- bit-identical to the whole-prompt pass for
                the overlapping rows (the exact-match gate in
                tests/test_serving_families.py, which chunks its
                prompts, relies on this).
    pallas /    ``kernels/attention.paged_prefill_attention``: block table
    interpret   scalar-prefetched to SMEM, grid (H, nq, pages), one pool
                page DMA'd per step via the BlockSpec index map; dead pages
                beyond the causal frontier are clamp-elided and skipped.
    ==========  ===========================================================
    """
    if kv_pages is not None and kv_pages < block_table.shape[0]:
        block_table = block_table[:kv_pages]
    if backend == "xla":
        from repro.models.attention import (PagedKVCache,
                                            paged_prefill_attention_xla)
        cache = PagedKVCache(k_pool, v_pool, block_table[None],
                             jnp.zeros((1,), jnp.int32), k_pool.shape[3],
                             layer)
        return paged_prefill_attention_xla(q, cache, start, window=window,
                                           softcap=softcap, scale=scale)
    from repro.kernels import attention as attn_kernel
    return attn_kernel.paged_prefill_attention(
        q, k_pool, v_pool, block_table, start, layer, window=window,
        softcap=softcap, scale=scale, interpret=(backend == "interpret"))


# -- mamba2 ssd ---------------------------------------------------------------
def ssd_impl(x, dt, a_log, b, c, *, d_skip=None, chunk: int = 256,
             initial_state=None, return_final_state: bool = False,
             backend: Backend = "xla"):
    """Mamba-2 SSD mixer. See kernels/mamba2.py for the chunked TPU kernel.
    Reached as ``ctx.ssd(...)``; under a mesh the batch B is sharded.

    ``initial_state``: (B, H, N, P) f32 recurrent state carried in from a
    previous segment (chunked prefill resumes here); ``return_final_state``
    additionally returns the (B, H, N, P) post-sequence state (the
    prefill->decode handoff).

    backend matrix (no tunable flags; ``chunk`` is the SSD decomposition
    granularity, a model hyperparameter rather than a tuned schedule):

    ==========  ===========================================================
    xla         ``models.ssm.ssd_chunked_xla``: intra-chunk einsums + the
                inter-chunk ``lax.scan``; the oracle structure and the
                serving/training reference (supports resumable
                ``initial_state`` for chunked prefill).
    pallas /    ``kernels/mamba2.ssd``: the same decomposition with the
    interpret   intra-chunk GEMMs lowered as Pallas kernels and the whole
                chunk-scan epilogue fused in-kernel (d_skip add + final
                state emitted from the VMEM state scratch -- no
                accumulator HBM round-trip). A non-None ``initial_state``
                demotes to the xla path: the kernel's VMEM scan always
                starts from zeros (resume is the serving reference's job,
                like the traced-window demotion in attention).
    ==========  ===========================================================
    """
    if backend == "xla" or initial_state is not None:
        from repro.models.ssm import _final_state, ssd_chunked_xla
        y = ssd_chunked_xla(x, dt, a_log, b, c, d_skip=d_skip, chunk=chunk,
                            initial_state=initial_state)
        if not return_final_state:
            return y
        _, fs = _final_state(x, dt, a_log, b, c, initial_state=initial_state)
        return y, fs
    from repro.kernels import mamba2 as m2
    return m2.ssd(x, dt, a_log, b, c, d_skip=d_skip, chunk=chunk,
                  interpret=(backend == "interpret"),
                  return_final_state=return_final_state)


# ---------------------------------------------------------------------------
# The PR-5 ``ops.<name>(..., backend=...)`` deprecation shims lived here for
# one release and are now gone: dispatch through
# ``repro.core.context.ExecutionContext`` (``ctx.gemm``, ``ctx.ssd``, ...).
# Lint rule GL506 (repro/analysis/lint/source.py) forbids reintroducing a
# top-level alias for any legacy name in this module.
# ---------------------------------------------------------------------------
