"""Per-kernel schedule spaces for the kernel-agnostic tuner.

PR 1 tuned one kernel class: the GEMM engine, whose schedule is a
:class:`~repro.core.tiling.TilePlan` (the "GemmPlan"). This module adds the
other two kernel classes the stack runs hot:

* :class:`AttnSchedule` -- flash attention's ``(block_q, block_k)`` blocking
  (kernels/attention.py). ``block_q`` sets the VMEM-resident query tile /
  online-softmax accumulator; ``block_k`` sets the streamed K/V tile.
* :class:`ConvSchedule` -- the implicit-im2col conv kernel's ``co_tile``
  (kernels/conv.py): the output-channel tile whose accumulator stays
  resident across the filter-tap stream.

Each space follows the GEMM tuner's contract so ``tune.tuner`` can drive any
of them through one measure/tiebreak path:

* a **lattice enumerator** (every candidate legal under the config's
  scratchpad/accumulator budgets, the static default always included),
* an **analytic cycle model** faithful to how the Pallas kernel lowers the
  schedule (the deterministic tiebreak when measured times tie),
* a **stable cache fingerprint** (``attn_cache_key`` / ``conv_cache_key``,
  sharing ``cache.kernel_fingerprint`` with the GEMM path).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from repro.core import isa
from repro.core.config import GemminiConfig, bytes_of
from repro.kernels.contracts import paged_heads_per_block
from repro.tune import cache as tcache

# Static defaults -- the schedules the kernels ship with when tuning is off
# (kernels/attention.py and kernels/conv.py keyword defaults).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
DEFAULT_CO_TILE = 128
DEFAULT_PAGE_SIZE = 64

# Candidate block sizes before clamping against the problem (the kernels
# clamp the same way: block = min(block, max(t, 8))).
_ATTN_BLOCKS = (64, 128, 256, 512, 1024)
_CO_TILES = (8, 16, 32, 64, 128, 256, 512)
_PAGE_SIZES = (8, 16, 32, 64, 128, 256, 512)

# Fixed per-grid-step cost (cycles) of the paged decode kernel: the block
# table SMEM read + DMA issue latency a tiny page cannot amortize. Rough,
# but it is what makes the page-size lattice non-degenerate on the analytic
# tiebreak (pure bandwidth/MAC terms would always pick the smallest page).
PAGE_STEP_CYCLES = 200.0


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def schedule_dtype(dtype):
    """Normalize a schedule's streamed dtype: accepts the engine's short
    names ("bf16", "fp32", ...) as well as anything numpy/jax understands,
    so fingerprints and byte widths agree no matter which spelling the
    caller used."""
    import jax.numpy as jnp
    if isinstance(dtype, str):
        from repro.core import config as _config
        try:
            return jnp.dtype(_config.dtype_of(dtype))
        except ValueError:
            pass
    return jnp.dtype(dtype)


def _macs_per_cycle(cfg: GemminiConfig) -> float:
    return cfg.dim * cfg.dim * (1.0 if cfg.pipeline_depth > 1 else 0.5)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnSchedule:
    """Flash-attention blocking: (block_q, block_k)."""

    block_q: int
    block_k: int

    def effective(self, tq: int, tk: int) -> "AttnSchedule":
        """Clamped exactly as kernels/attention.py clamps at launch."""
        return AttnSchedule(min(self.block_q, max(tq, 8)),
                            min(self.block_k, max(tk, 8)))


def default_attn_schedule() -> AttnSchedule:
    return AttnSchedule(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def _attn_fits(cfg: GemminiConfig, bq: int, bk: int, d: int,
               in_bytes: int) -> bool:
    # Streamed per KV step: one K and one V tile (double-buffered).
    streamed = cfg.pipeline_depth * 2 * bk * d * in_bytes
    # Resident across the KV stream: q tile + f32 accumulator + (m, l) state.
    resident = bq * d * (in_bytes + 4) + 2 * bq * 4
    return (streamed <= cfg.scratchpad_bytes
            and resident <= cfg.accumulator_bytes)


def attn_cycles(sched: AttnSchedule, cfg: GemminiConfig, b: int, h: int,
                kvh: int, tq: int, tk: int, d: int, *, causal: bool,
                window: Optional[int], in_bytes: int,
                sys: Optional[isa.SystemParams] = None) -> float:
    """Deterministic cost of the schedule as kernels/attention.py runs it.

    Counts only *live* (q-block, kv-block) pairs -- the kernel's whole-block
    skip predicate (``attention.block_live``, including the pad_k term) is
    re-evaluated here so a schedule whose blocking skips more fully-masked
    work ranks better, which is the kernel-level reason sliding-window
    layers prefer block_k <= window.
    """
    sys = sys or isa.ROCKET
    eff = sched.effective(tq, tk)
    bq, bk = eff.block_q, eff.block_k
    nq, nk = _ceil_div(tq, bq), _ceil_div(tk, bk)
    # Live (i, j) pairs counted in O(nq): for each q block the live j form
    # one interval [j_lo, j_hi] under ``attention.block_live``'s terms
    # (padding: k0 < tk; causal: k0 <= q0 + bq - 1; window: k0 + bk - 1 >
    # q0 - window) -- a 128k-context schedule must not cost nq*nk Python
    # iterations per candidate.
    live = 0
    for i in range(nq):
        q0 = i * bq + (tk - tq)
        hi_k = tk - 1
        if causal:
            hi_k = min(hi_k, q0 + bq - 1)
        j_hi = min(nk - 1, hi_k // bk) if hi_k >= 0 else -1
        j_lo = 0
        if window is not None:
            # smallest j with j*bk + bk - 1 > q0 - window
            j_lo = max(0, -(-(q0 - window - bk + 2) // bk))
        live += max(0, j_hi - j_lo + 1)
    # Two MXU contractions per live block: Q@K^T and P@V.
    macs = 2 * b * h * live * bq * bk * d
    # K/V fetched per live block; the q tile is fetched once per q row
    # (its block index is constant across the KV stream, so Mosaic's
    # revisiting elides the re-copy).
    loads = b * h * (live * 2 * bk * d + nq * bq * d) * in_bytes
    stores = b * h * nq * bq * d * in_bytes
    bw = sys.effective_bw(cfg.dim)
    return max(macs / _macs_per_cycle(cfg), loads / bw, stores / bw)


def enumerate_attn_schedules(cfg: GemminiConfig, b: int, h: int, kvh: int,
                             tq: int, tk: int, d: int, *, causal: bool = True,
                             window: Optional[int] = None,
                             in_bytes: int = 2,
                             max_candidates: int = 16) -> List[AttnSchedule]:
    """Legal (block_q, block_k) lattice, analytic-cost ordered.

    Candidates are the *effective* (problem-clamped) block sizes, so two
    nominal schedules that clamp to the same launch parameters dedupe. The
    static default (clamped) is always included.
    """
    def axis(t: int) -> List[int]:
        return sorted({min(p, max(t, 8)) for p in _ATTN_BLOCKS})

    default = default_attn_schedule().effective(tq, tk)
    scheds = {default}
    for bq in axis(tq):
        for bk in axis(tk):
            if _attn_fits(cfg, bq, bk, d, in_bytes):
                scheds.add(AttnSchedule(bq, bk))
    ordered = sorted(
        scheds,
        key=lambda s: (attn_cycles(s, cfg, b, h, kvh, tq, tk, d,
                                   causal=causal, window=window,
                                   in_bytes=in_bytes),
                       -s.block_q, -s.block_k))
    ordered = ordered[:max_candidates]
    if default not in ordered:
        ordered[-1] = default
    return ordered


def attn_cache_key(cfg: GemminiConfig, b: int, tq: int, tk: int, h: int,
                   kvh: int, d: int, *, causal: bool,
                   window: Optional[int], dtype) -> str:
    """Stable fingerprint for an attention schedule lookup.

    Everything that changes the legal lattice or the live-block ranking is
    in the payload: problem shape, GQA grouping, masking structure, and the
    streamed dtype (q/k/v storage width; softcap is elementwise and
    schedule-neutral, so it is excluded).
    """
    payload = {
        "b": int(b), "tq": int(tq), "tk": int(tk),
        "h": int(h), "kvh": int(kvh), "d": int(d),
        "causal": bool(causal),
        "win": int(window) if window else 0,
        "dtype": schedule_dtype(dtype).name,
    }
    # Attention consults only the VMEM budgets / dim / pipelining: the
    # engine's GEMM dtypes and tile caps must not discriminate, or a warm
    # pass under a quantized engine config would key entries a bf16-default
    # request path never hits.
    return tcache.kernel_fingerprint("attn", cfg, payload,
                                     engine_dtypes=False, tile_caps=False)


# ---------------------------------------------------------------------------
# paged attention (serving decode)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PagedAttnSchedule:
    """Paged-decode blocking: the KV page size (tokens per cache block).

    Unlike the other schedule spaces this one is *allocation-coupled*: the
    page size is baked into the serving engine's pool shapes at startup
    (``serving.PagedKVAllocator``), and the kernel streams one page of a
    block of KV heads per grid step. The lattice therefore trades kernel
    efficiency (bigger pages amortize the per-step table-read/DMA overhead)
    against allocator
    efficiency (bigger pages waste ~page/2 tokens of HBM per request to
    internal fragmentation, shrinking the number of co-resident requests).
    """

    page_size: int

    def effective(self, max_context: int) -> "PagedAttnSchedule":
        return PagedAttnSchedule(max(8, min(self.page_size, max_context)))


def default_paged_schedule() -> PagedAttnSchedule:
    return PagedAttnSchedule(DEFAULT_PAGE_SIZE)


def _paged_fits(cfg: GemminiConfig, page: int, rep: int, d: int,
                in_bytes: int) -> bool:
    # Streamed per page step: one K and one V page of at least one KV head
    # (double-buffered); the kernel takes more heads where they fit.
    streamed = cfg.pipeline_depth * 2 * page * d * in_bytes
    # Resident across the stream: the (rep, D) query rows + f32 accumulator
    # + (m, l) state, as kernels/attention._paged_decode_kernel holds them.
    resident = rep * d * (in_bytes + 4) + 2 * rep * 4
    return (streamed <= cfg.scratchpad_bytes
            and resident <= cfg.accumulator_bytes)


def paged_attn_cycles(sched: PagedAttnSchedule, cfg: GemminiConfig, b: int,
                      h: int, kvh: int, d: int, max_context: int, *,
                      window: Optional[int], in_bytes: int,
                      mean_len: Optional[int] = None,
                      sys: Optional[isa.SystemParams] = None) -> float:
    """Deterministic decode-step cost as the paged kernel runs it, at a
    representative request length (``mean_len``, default max_context/2).

    Live pages per request follow ``attention.block_live`` with block_q=1:
    ceil(len/page) minus the pages a sliding window lets the kernel skip.
    Each grid step takes one live page of a block of KV heads
    (``contracts.paged_heads_per_block``), so the fixed per-step cost is
    charged per (slot, head block, live page).
    The fragmentation penalty models the allocator side: the last page of
    every request is half-wasted on average, which at a fixed HBM budget
    evicts-or-queues proportionally more co-resident requests, so it is
    charged as extra amortized traffic.
    """
    sys = sys or isa.ROCKET
    eff = sched.effective(max_context)
    page = eff.page_size
    rep = h // kvh
    ln = mean_len if mean_len is not None else max(1, max_context // 2)
    pos = ln - 1
    j_hi = pos // page
    j_lo = 0
    if window is not None:
        # smallest j with j*page + page - 1 > pos - window (block_live's
        # window term at block_q = 1)
        j_lo = max(0, -(-(pos - window - page + 2) // page))
    live = max(1, j_hi - j_lo + 1)
    # Two MXU contractions per live page: Q@K^T and P@V on (rep, page, d).
    macs = 2 * b * kvh * live * rep * page * d
    loads = b * kvh * live * 2 * page * d * in_bytes
    # Internal fragmentation: ~page/2 dead tokens resident per request,
    # charged at the K+V byte cost they occupy in the budget.
    frag = b * kvh * (page / 2) * 2 * d * in_bytes
    bw = sys.effective_bw(cfg.dim)
    compute = max(macs / _macs_per_cycle(cfg), (loads + frag) / bw)
    hb = paged_heads_per_block(cfg, kvh=kvh, rep=rep, page=page, d=d,
                               itemsize=in_bytes)
    return compute + b * (kvh // hb) * live * PAGE_STEP_CYCLES


def enumerate_paged_schedules(cfg: GemminiConfig, b: int, h: int, kvh: int,
                              d: int, max_context: int, *,
                              window: Optional[int] = None,
                              in_bytes: int = 2,
                              max_candidates: int = 8
                              ) -> List[PagedAttnSchedule]:
    """Legal page-size lattice, analytic-cost ordered; the clamped static
    default always survives (the GEMM solver's minimal-tile guarantee)."""
    rep = h // kvh
    default = default_paged_schedule().effective(max_context)
    scheds = {default}
    for p in _PAGE_SIZES:
        s = PagedAttnSchedule(p).effective(max_context)
        if _paged_fits(cfg, s.page_size, rep, d, in_bytes):
            scheds.add(s)
    ordered = sorted(
        scheds,
        key=lambda s: (paged_attn_cycles(s, cfg, b, h, kvh, d, max_context,
                                         window=window, in_bytes=in_bytes),
                       -s.page_size))
    ordered = ordered[:max_candidates]
    if default not in ordered:
        ordered[-1] = default
    return ordered


def paged_attn_cache_key(cfg: GemminiConfig, b: int, h: int, kvh: int,
                         d: int, max_context: int, *,
                         window: Optional[int], dtype) -> str:
    """Stable fingerprint for a paged-schedule lookup. Like the dense
    attention key: only the VMEM budgets / dim / pipelining discriminate
    on the config side (the kernel streams the model dtype regardless of
    the engine's GEMM datapath)."""
    payload = {
        "b": int(b), "h": int(h), "kvh": int(kvh), "d": int(d),
        "ctx": int(max_context),
        "win": int(window) if window else 0,
        "dtype": schedule_dtype(dtype).name,
    }
    return tcache.kernel_fingerprint("paged_attn", cfg, payload,
                                     engine_dtypes=False, tile_caps=False)


# ---------------------------------------------------------------------------
# conv (implicit im2col)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ConvSchedule:
    """Implicit-im2col conv blocking: the output-channel tile."""

    co_tile: int

    def effective(self, co: int) -> "ConvSchedule":
        return ConvSchedule(min(self.co_tile, co))


def default_conv_schedule() -> ConvSchedule:
    return ConvSchedule(DEFAULT_CO_TILE)


def _conv_dims(h: int, w: int, kh: int, kw: int, stride: int, padding: int):
    """(oh, ow, hp, wp): output dims + the VMEM-resident input block dims
    (exact tap cover, as kernels/conv.py trims it)."""
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    return oh, ow, (oh - 1) * stride + kh, (ow - 1) * stride + kw


def _conv_fits(cfg: GemminiConfig, co_tile: int, oh: int, ow: int, ci: int,
               hp: int, wp: int) -> bool:
    in_b = bytes_of(cfg.input_dtype)
    acc_b = bytes_of(cfg.acc_dtype)
    # Resident: the (oh*ow, co_tile) accumulator at acc width.
    if oh * ow * co_tile * acc_b > cfg.accumulator_bytes:
        return False
    # Streamed/resident in the scratchpad: the whole input block for the tap
    # stream + the double-buffered per-tap weight tile.
    streamed = hp * wp * ci * in_b + cfg.pipeline_depth * ci * co_tile * in_b
    return streamed <= cfg.scratchpad_bytes


def conv_cycles(sched: ConvSchedule, cfg: GemminiConfig, n: int, h: int,
                w: int, ci: int, co: int, kh: int, kw: int, *,
                stride: int = 1, padding: int = 0, has_bias: bool = False,
                sys: Optional[isa.SystemParams] = None) -> float:
    """Cost of the schedule as kernels/conv.py lowers it: grid
    (N, ceil(co/co_tile), KH*KW), the input block re-fetched per co tile,
    the weight tap tile per grid step, padded-co MACs wasted."""
    sys = sys or isa.ROCKET
    ct = sched.effective(co).co_tile
    nco = _ceil_div(co, ct)
    oh, ow, hp, wp = _conv_dims(h, w, kh, kw, stride, padding)
    in_b = bytes_of(cfg.input_dtype)
    acc_b = bytes_of(cfg.acc_dtype)
    macs = n * nco * kh * kw * oh * ow * ci * ct
    loads = n * nco * (hp * wp * ci * in_b + kh * kw * ci * ct * in_b)
    if has_bias:
        loads += n * nco * ct * acc_b
    stores = n * oh * ow * nco * ct * bytes_of(cfg.output_dtype)
    bw = sys.effective_bw(cfg.dim)
    return max(macs / _macs_per_cycle(cfg), loads / bw, stores / bw)


def enumerate_conv_schedules(cfg: GemminiConfig, n: int, h: int, w: int,
                             ci: int, co: int, kh: int, kw: int, *,
                             stride: int = 1, padding: int = 0,
                             has_bias: bool = False,
                             max_candidates: int = 12) -> List[ConvSchedule]:
    """Legal co_tile lattice (power-of-two tiles clamped to co, plus co
    itself), analytic-cost ordered; the clamped static default is always
    included, and the smallest tile survives even when budgets exclude all
    (mirror of the GEMM solver's minimal-tile guarantee)."""
    oh, ow, hp, wp = _conv_dims(h, w, kh, kw, stride, padding)
    cands = sorted({min(t, co) for t in _CO_TILES} | {co})
    legal = [ConvSchedule(c) for c in cands
             if _conv_fits(cfg, c, oh, ow, ci, hp, wp)]
    if not legal:
        legal = [ConvSchedule(cands[0])]
    default = default_conv_schedule().effective(co)
    if default not in legal and _conv_fits(cfg, default.co_tile, oh, ow,
                                           ci, hp, wp):
        legal.append(default)
    ordered = sorted(
        legal,
        key=lambda s: (conv_cycles(s, cfg, n, h, w, ci, co, kh, kw,
                                   stride=stride, padding=padding,
                                   has_bias=has_bias),
                       -s.co_tile))
    ordered = ordered[:max_candidates]
    if default not in ordered and default in legal:
        ordered[-1] = default
    return ordered


def conv_cache_key(cfg: GemminiConfig, n: int, h: int, w: int, ci: int,
                   co: int, kh: int, kw: int, *, stride: int, padding: int,
                   has_bias: bool) -> str:
    payload = {
        "nhwc": [int(n), int(h), int(w), int(ci)],
        "co": int(co), "khw": [int(kh), int(kw)],
        "stride": int(stride), "pad": int(padding),
        "bias": bool(has_bias),
    }
    # The conv kernel runs at the engine dtypes (x/w at input, accumulator
    # at acc) so they stay in the key; the GEMM-only max_tile caps do not.
    return tcache.kernel_fingerprint("conv", cfg, payload, tile_caps=False)
