"""Flash attention as a Pallas TPU kernel (the LM-serving hot spot).

Supports everything the assigned architectures need: grouped-query attention
(any H/KVH ratio incl. MQA), causal masking, sliding-window "local" layers,
gemma-2 logit soft-capping, and non-square Tq != Tk (cache-backed prefill).

Schedule (TPU-native, re-derived for HBM->VMEM->MXU per DESIGN.md):
  grid = (B, H, nq, nk) with the KV axis innermost ("arbitrary" = sequential,
  enabling the carried online-softmax state). The q tile is resident in VMEM
  across the KV stream -- this is exactly the Gemmini *output-stationary*
  dataflow applied to attention: the output accumulator (acc, m, l) stays in
  the wide-precision scratch while K/V tiles stream past, and the epilogue
  (1/l normalization) runs on the last KV step, like the OS GEMM's
  rounding-shift epilogue on the last K step.

Block-skipping: fully-masked KV blocks (beyond the causal frontier, outside
the sliding window, or entirely in the pad_k zero-padding past the true
sequence) are skipped via ``pl.when``, so local-attention layers do
O(T*window) work, not O(T^2) -- the kernel-level reason gemma3's 5:1
local:global pattern makes 128k context affordable. ``block_live`` is the
single skip predicate shared by both kernels and by the tuner's analytic
cost model (``tune.schedules.attn_cycles``).

Fusion audit note (ROADMAP): the epilogue is already fused -- the
1/l finalize reads the f32 (acc, m, l) scratch and writes the output tile
in-kernel on the last KV step; the accumulator never round-trips HBM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import GemminiConfig
from repro.kernels.contracts import kernel_contract, paged_heads_per_block

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def block_live(k0, q0, *, block_q: int, block_k: int, tk: int,
               causal: bool, window: Optional[int]):
    """Whole-block liveness: some (qpos, kpos) pair in the (q0.., k0..)
    block is unmasked. Works on Python ints (tuner cost model) and traced
    values (kernel ``pl.when`` predicate) alike:

      padding: k0 < tk                       (block not fully in pad_k)
      causal:  k0 <= q0 + block_q - 1
      window:  k0 + block_k - 1 > q0 - window
    """
    live = k0 < tk
    if causal:
        live = live & (k0 <= q0 + block_q - 1)
    if window is not None:
        live = live & (k0 + block_k - 1 > q0 - window)
    return live


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 nk: int, block_q: int, block_k: int, tq: int, tk: int,
                 causal: bool, window: Optional[int],
                 softcap: Optional[float], scale: float):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions; queries are right-aligned against the keys
    q0 = i * block_q + (tk - tq)
    k0 = j * block_k

    # ---- whole-block skip test (static-shape friendly) -------------------
    # The k0 < tk padding term matters for non-causal/no-window layers:
    # without it every fully-padded KV block (the pad_k region) still runs
    # the MXU and relies on the -inf mask to zero its contribution.
    live = block_live(k0, q0, block_q=block_q, block_k=block_k, tk=tk,
                      causal=causal, window=window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < tk                                   # kv padding
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@kernel_contract("flash_attention")
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Tq, H, D); k/v: (B, Tk, KVH, D); returns (B, Tq, H, D).

    ``window``: sliding-window size for local layers (None = global).
    """
    b, tq, h, d = q.shape
    _, tk, kvh, _ = k.shape
    if h % kvh != 0:
        raise ValueError(f"H={h} not a multiple of KVH={kvh}")
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    block_q = min(block_q, max(tq, 8))
    block_k = min(block_k, max(tk, 8))
    nq = -(-tq // block_q)
    nk = -(-tk // block_k)
    pad_q = nq * block_q - tq
    pad_k = nk * block_k - tk

    # (B, H, T, D) layout: last-two-dim tiles are (block, D) -- MXU-aligned.
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    rep = h // kvh
    kernel = functools.partial(
        _attn_kernel, nk=nk, block_q=block_q, block_k=block_k,
        tq=tq, tk=tk, causal=causal, window=window, softcap=softcap,
        scale=sc)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, i, j: (bb, hh, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, i, j: (bb, hh // rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, i, j: (bb, hh // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, i, j: (bb, hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :tq]
    return jnp.moveaxis(out, 1, 2)   # back to (B, Tq, H, D)


# ---------------------------------------------------------------------------
# single-token decode kernel: one query row vs a long KV cache
# ---------------------------------------------------------------------------
def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, nk: int, block_k: int, tk: int,
                   window: Optional[int], softcap: Optional[float],
                   scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = len_ref[pl.program_id(0)]      # current position (keys <= pos live)
    k0 = j * block_k
    # Same skip predicate as the prefill kernel with q0 = pos and block_q=1;
    # the k0 < tk padding term skips blocks fully in the pad_k region (pos
    # is caller-supplied, so do not rely on pos < tk to imply it).
    live = (k0 < tk) & (k0 <= pos)
    if window is not None:
        live = live & (k0 + block_k - 1 > pos - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (H, D) heads tile
        k = k_ref[0].astype(jnp.float32)                   # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (H, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@kernel_contract("decode_attention")
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     pos: jnp.ndarray, *, window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None, block_k: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, D) vs cache k/v: (B, S, KVH, D); pos: scalar int32.

    The per-(batch) grid streams KV blocks while the H query rows stay
    resident; MQA/GQA is handled by flattening each query-group's heads into
    the rows of a single (H_per_group, D) matmul tile.
    """
    b, tq, h, d = q.shape
    assert tq == 1
    _, s, kvh, _ = k.shape
    rep = h // kvh
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, s)
    nk = -(-s // block_k)
    pad_k = nk * block_k - s

    # (B*KVH, rep, D) query rows; (B*KVH, S, D) caches
    qg = q[:, 0].reshape(b, kvh, rep, d).reshape(b * kvh, rep, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * kvh, s, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * kvh, s, d)
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_k), (0, 0)))
    lens = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b * kvh,))

    kernel = functools.partial(_decode_kernel, nk=nk, block_k=block_k, tk=s,
                               window=window, softcap=softcap, scale=sc)
    out = pl.pallas_call(
        kernel,
        grid=(b * kvh, nk),
        in_specs=[
            pl.BlockSpec((1, rep, d), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, j: (g, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),      # all lengths
        ],
        out_specs=pl.BlockSpec((1, rep, d), lambda g, j: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, rep, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kt, vt, lens)
    return out.reshape(b, kvh * rep, d)[:, None].reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# paged-attention decode kernel: gather KV pages via per-request block tables
# ---------------------------------------------------------------------------
def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, npages: int, page: int,
                         page_axis: int, window: Optional[int],
                         softcap: Optional[float], scale: float):
    b = pl.program_id(0)
    j = pl.program_id(page_axis)         # logical page index within the seq

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = len_ref[b]                      # live tokens incl. the current one
    pos = ln - 1
    k0 = j * page
    # The shared whole-block predicate with block_q = 1 (one query row): the
    # padding term k0 < ln skips pages past the request's frontier entirely
    # -- dead and never-allocated table slots do no MXU work. An empty slot
    # (ln == 0) has no live page at all; _finalize's l == 0 guard then
    # yields a zero row the engine ignores.
    live = block_live(k0, pos, block_q=1, block_k=page, tk=ln,
                      causal=True, window=window)

    @pl.when(live)
    def _compute():
        # Every head of the block at once: q (hb, rep, D) against the
        # page's K and V (hb, page, D), two MXU products batched over the
        # heads. q.K^T takes the stored operands and accumulates in f32
        # (their products are exact there), so the scale follows the
        # product; the softmax and P.V stay in f32.
        s = jax.lax.dot_general(q_ref[0], k_ref[:, 0],
                                (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]                                  # (hb, rep)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + jax.lax.dot_general(
            p, v_ref[:, 0].astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == npages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@kernel_contract("paged_decode_attention")
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           lengths: jnp.ndarray, layer, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           cfg: Optional[GemminiConfig] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Single-token decode against a *paged* KV cache.

    q: (B, 1, H, D); k_pool/v_pool: (L, KVH, NP, page, D), every layer's
    shared page pools stacked; layer: scalar int32 (may be traced), the
    layer whose pages this call reads; block_tables: (B, MP) int32 page ids
    mapping request positions [j*page, (j+1)*page) to pool page
    ``block_tables[b, j]``; lengths: (B,) int32 live tokens per request
    (the current token included -- write the KV of the new token first,
    then attend); cfg: the VMEM budgets (``GemminiConfig()``'s when None).

    The gather happens *inside* the kernel: each grid step's K/V BlockSpec
    index map reads the block table (scalar-prefetched into SMEM) and DMAs
    one page of layer ``layer`` for a block of KV heads into VMEM --
    neither a request's pages nor a layer's pool is ever materialized in
    HBM, which is the whole point of paging. The block holds every KV head
    where its double-buffered K and V pages fit the scratchpad
    (``contracts.paged_heads_per_block``), so the grid is (b, j); else the
    largest divisor of KVH that fits, and the grid is (b, g, j). The
    stacks enter as their free ``(L*KVH, NP, page, D)`` view and ``layer``
    rides at the end of the prefetched lengths, so a block of ``hb`` rows
    starts at row ``layer*KVH + g*hb``: its block index is
    ``layer*(KVH/hb) + g``. Dead logical pages (j past the request
    frontier) clamp their index map to the last live page, so Mosaic's
    block-revisiting elides the re-copy, and the ``block_live`` predicate
    skips their compute.
    """
    b, tq, h, d = q.shape
    assert tq == 1
    n_layers, kvh, npool, page, _ = k_pool.shape
    mp = block_tables.shape[1]
    rep = h // kvh
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    hb = paged_heads_per_block(cfg or GemminiConfig(), kvh=kvh, rep=rep,
                               page=page, d=d,
                               itemsize=jnp.dtype(k_pool.dtype).itemsize)
    nhb = kvh // hb

    qg = q[:, 0].reshape(b, kvh, rep, d)
    bt = block_tables.reshape(-1).astype(jnp.int32)          # (B*MP,)
    # (B + 1,): the slots' lengths, then the layer index (at position b)
    lens = jnp.concatenate([lengths.astype(jnp.int32),
                            jnp.asarray(layer, jnp.int32).reshape((1,))])
    kf = k_pool.reshape(n_layers * kvh, npool, page, d)
    vf = v_pool.reshape(n_layers * kvh, npool, page, d)

    grid = (b, mp) if nhb == 1 else (b, nhb, mp)

    def _coords(*args):
        # (bb, g, j, bt_ref, len_ref) whatever the grid's rank
        return (args[0], 0, *args[1:]) if nhb == 1 else args

    def _head_index(*args):
        bb, g, _, _, _ = _coords(*args)
        return (bb, g, 0, 0)

    def _page_index(*args):
        bb, g, j, bt_ref, len_ref = _coords(*args)
        # Clamp dead j to the request's last live page: same block index ->
        # Mosaic elides the DMA; an empty request (len 0) pins page bt[b,0].
        jmax = jnp.maximum(len_ref[bb] - 1, 0) // page
        return (len_ref[b] * nhb + g,
                bt_ref[bb * mp + jnp.minimum(j, jmax)], 0, 0)

    kernel = functools.partial(_paged_decode_kernel, npages=mp, page=page,
                               page_axis=len(grid) - 1, window=window,
                               softcap=softcap, scale=sc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, rep, d), _head_index),
            pl.BlockSpec((hb, 1, page, d), _page_index),
            pl.BlockSpec((hb, 1, page, d), _page_index),
        ],
        out_specs=pl.BlockSpec((1, hb, rep, d), _head_index),
        scratch_shapes=[
            pltpu.VMEM((hb, rep), jnp.float32),
            pltpu.VMEM((hb, rep), jnp.float32),
            pltpu.VMEM((hb, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",)),
        interpret=interpret,
    )(bt, lens, qg, kf, vf)
    return out.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# paged-attention chunked-prefill kernel: a fresh chunk of queries vs
# cache pages + itself, gathered via the request's block table
# ---------------------------------------------------------------------------
def _paged_prefill_kernel(bt_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, mp: int, page: int,
                          block_q: int, tq: int, window: Optional[int],
                          softcap: Optional[float], scale: float):
    i = pl.program_id(1)                 # q block within the chunk
    j = pl.program_id(2)                 # logical page index within the seq

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[0]                 # chunk's first logical position
    q0 = start + i * block_q
    k0 = j * page
    # Shared whole-block predicate: the frontier (start + tq, the chunk's
    # own KV was scattered before this call) plays the tk padding role, so
    # never-written logical pages do no MXU work; causal + window terms
    # skip exactly as in the prefill kernel.
    live = block_live(k0, q0, block_q=block_q, block_k=page, tk=start + tq,
                      causal=True, window=window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == mp - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@kernel_contract("paged_prefill_attention")
def paged_prefill_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, block_table: jnp.ndarray,
                            start: jnp.ndarray, layer, *,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            block_q: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    """Chunked-prefill attention against a *paged* KV cache.

    q: (1, T, H, D), one request's fresh chunk of queries at logical
    positions [start, start + T); k_pool/v_pool: (L, KVH, NP, page, D),
    every layer's shared page pools stacked, with the chunk's own KV
    already scattered in (write first, then attend); block_table: (MP,)
    int32 page ids for THIS request; start: scalar int32 (traced -- one
    compile serves every chunk offset); layer: scalar int32 (may be
    traced), the layer whose pages this call reads.

    The block-table gather of ``paged_decode_attention`` extended to a
    whole query tile: grid (H, nq, MP) with the page axis innermost, each
    step's K/V BlockSpec index map reading the scalar-prefetched table to
    DMA one page of layer ``layer`` into VMEM (the stacks enter as their
    free ``(L*KVH, NP, page, D)`` view; ``layer`` rides after ``start`` in
    the second prefetched array, so a step reads row ``layer*KVH + kvh``).
    Dead logical pages (beyond what q block i can see under the causal
    frontier) clamp their index map to the last visible page so Mosaic's
    block-revisiting elides the copy, and the shared ``block_live``
    predicate skips their compute -- a chunk at position s does O(s + T)
    page work, not O(MP).
    """
    b, tq, h, d = q.shape
    assert b == 1, "chunked prefill is per-request (one slot per call)"
    n_layers, kvh, npool, page, _ = k_pool.shape
    mp = block_table.shape[0]
    rep = h // kvh
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    block_q = min(block_q, max(tq, 8))
    nq = -(-tq // block_q)
    pad_q = nq * block_q - tq
    qt = jnp.moveaxis(q[0], 1, 0)                          # (H, T, D)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
    bt = block_table.reshape(-1).astype(jnp.int32)
    start_arr = jnp.stack([jnp.asarray(start, jnp.int32),
                           jnp.asarray(layer, jnp.int32)])   # (start, layer)
    kf = k_pool.reshape(n_layers * kvh, npool, page, d)
    vf = v_pool.reshape(n_layers * kvh, npool, page, d)

    def _page_index(hh, i, j, bt_ref, start_ref):
        # Clamp dead j to the last page visible from q block i (or the
        # chunk frontier, whichever is nearer): same block index -> Mosaic
        # elides the DMA, and the table is never read out of range.
        qmax = start_ref[0] + (i + 1) * block_q - 1
        jmax = jnp.minimum(qmax, start_ref[0] + tq - 1) // page
        return (start_ref[1] * kvh + hh // rep,
                bt_ref[jnp.minimum(j, jmax)], 0, 0)

    kernel = functools.partial(
        _paged_prefill_kernel, mp=mp, page=page, block_q=block_q, tq=tq,
        window=window, softcap=softcap, scale=sc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, nq, mp),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda hh, i, j, bt_ref, start_ref: (hh, i, 0)),
            pl.BlockSpec((1, 1, page, d), _page_index),
            pl.BlockSpec((1, 1, page, d), _page_index),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d),
            lambda hh, i, j, bt_ref, start_ref: (hh, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, nq * block_q, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bt, start_arr, qt, kf, vf)
    return jnp.moveaxis(out[:, :tq], 0, 1)[None]           # (1, T, H, D)
