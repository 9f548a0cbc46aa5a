"""GQA attention: blockwise (flash-style) XLA path + KV-cache decode.

Features required by the assigned architectures:
  * grouped-query attention (any H/KVH ratio, incl. MQA kv=1 for gemma3-1b)
  * sliding-window "local" layers interleaved with "global" layers
    (gemma2 1:1, gemma3 5:1)
  * logit soft-capping (gemma2)
  * QKV bias (qwen1.5)
  * decode against a (possibly sequence-sharded) KV cache; local layers
    only attend within the window.

The train/prefill path is blockwise with an online-softmax running state so
the 32k-prefill dry-run never materializes an S x S score matrix; this same
schedule is what kernels/attention.py implements in Pallas for TPU.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.generator import GemminiInstance
from repro.models import layers

Params = Dict[str, Any]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attn_init(key, d: int, n_heads: int, n_kv: int, head_dim: int, *,
              qkv_bias: bool = False, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], d, n_heads * head_dim, dtype=dtype),
        "wk": layers.dense_init(ks[1], d, n_kv * head_dim, dtype=dtype),
        "wv": layers.dense_init(ks[2], d, n_kv * head_dim, dtype=dtype),
        "wo": layers.dense_init(ks[3], n_heads * head_dim, d, dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _qkv(engine, p, x, n_heads, n_kv, head_dim):
    b, t, _ = x.shape
    q = layers.project(engine, x, p["wq"], p.get("bq"))
    k = layers.project(engine, x, p["wk"], p.get("bk"))
    v = layers.project(engine, x, p["wv"], p.get("bv"))
    return (q.reshape(b, t, n_heads, head_dim),
            k.reshape(b, t, n_kv, head_dim),
            v.reshape(b, t, n_kv, head_dim))


# ---------------------------------------------------------------------------
# blockwise attention (train / prefill)
# ---------------------------------------------------------------------------
def blockwise_attention_xla(q, k, v, *, causal: bool = True,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            block_k: int = 1024,
                            q_offset=None,
                            kv_len=None) -> jnp.ndarray:
    """Online-softmax attention, scanning over KV blocks.

    q: (B, Tq, H, D), k/v: (B, Tk, KVH, D). Memory is O(Tq * block_k).

    ``q_offset``: global position of query row 0 (may be traced). The
    default right-aligns queries against the keys (``Tk - Tq``), which is
    the train/prefill/cache-backed case; chunked prefill passes the chunk's
    start position explicitly. ``kv_len``: number of live keys (may be
    traced); defaults to ``Tk``. Keys at positions >= ``kv_len`` are
    masked, which makes over-allocated gather buffers (paged tables) safe.
    """
    b, tq, h, d = q.shape
    _, tk, kvh, _ = k.shape
    rep = h // kvh
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    # Clamp the KV block to a 128-multiple of the actual key length:
    # serving-scale contexts (tens to hundreds of keys) would otherwise
    # zero-pad to a full 1024-key block and burn >2x the scores/PV FLOPs
    # on provably-dead keys. Chunked-vs-single-pass bit-exactness is
    # preserved whenever both paths round to the same padded length
    # (equal-length blocks run the identical op sequence; trailing dead
    # keys are exact no-ops under the online-softmax update).
    block_k = min(block_k, -(-max(tk, 1) // 128) * 128)
    nb = -(-tk // block_k)
    pad = nb * block_k - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nb, block_k, kvh, d)
    vb = v.reshape(b, nb, block_k, kvh, d)

    qf = q.astype(jnp.float32) * sc
    if q_offset is None:
        q_offset = tk - tq                                 # right-aligned
    if kv_len is None:
        kv_len = tk
    qpos = jnp.arange(tq) + q_offset                       # global positions

    def body(carry, inp):
        m, l, acc = carry                                  # (B,H,Tq) ,, (B,H,Tq,D)
        kblk, vblk, bidx = inp                             # (B,block,KVH,D)
        kpos = bidx * block_k + jnp.arange(block_k)
        kh = jnp.repeat(kblk, rep, axis=2)                 # (B,block,H,D)
        vh = jnp.repeat(vblk, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kh.astype(jnp.float32))
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos[None, :] <= kv_len - 1                 # in-bounds (padding)
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vh.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    a0 = jnp.zeros((b, h, tq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,Tq,H,D)


# ---------------------------------------------------------------------------
# decode attention against a cache
# ---------------------------------------------------------------------------
class KVCache(NamedTuple):
    k: jnp.ndarray        # (B, S, KVH, D)
    v: jnp.ndarray        # (B, S, KVH, D)


class PagedKVCache(NamedTuple):
    """One layer's view of the paged KV cache: the stacked page pools, the
    layer it reads and writes, and the per-slot tables.

    The pools are the serving engine's whole HBM page arena, every layer's
    pools stacked (allocated once against the config's HBM budget); a
    layer scan carries the stacks and names its layer by ``layer``, so the
    writes land in place and attention reads the layer's pages straight
    from the stack -- no layer's pool is ever sliced out.
    ``tables``/``lengths`` describe every decode slot's view into them.
    ``page`` rides along as a static int so model code never re-derives it
    from shapes. For decode, ``active`` masks live slots and ``trash``
    names the reserved spill page retired slots write to (see
    ``paged_update_decode``); prefill ignores both.
    """

    k: jnp.ndarray             # (L, KVH, NP, page, D) stacked page pools
    v: jnp.ndarray             # (L, KVH, NP, page, D)
    tables: jnp.ndarray        # (B, MP) int32 page ids per slot
    lengths: jnp.ndarray       # (B,) int32 tokens already cached per slot
    page: int                  # static page size (tokens per page)
    layer: Any                 # scalar int32 (may be traced): the layer
    active: Optional[jnp.ndarray] = None   # (B,) bool decode-slot liveness
    trash: int = 0                         # reserved spill page id


def decode_attention(q, cache: KVCache, pos, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention. q: (B, 1, H, D); pos: scalar current position.

    Works with a sequence-sharded cache: the masked einsum contracts the full
    S axis; XLA inserts the partial-softmax all-reduce.
    """
    b, tq, h, d = q.shape
    _, s, kvh, _ = cache.k.shape
    rep = h // kvh
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    kpos = jnp.arange(s)
    mask = kpos <= pos
    if window is not None:
        mask = mask & (kpos > pos - window)

    kh = jnp.repeat(cache.k, rep, axis=2)
    vh = jnp.repeat(cache.v, rep, axis=2)
    sl = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * sc,
                    kh.astype(jnp.float32))
    if softcap is not None:
        sl = softcap * jnp.tanh(sl / softcap)
    sl = jnp.where(mask[None, None, None], sl, _NEG_INF)
    p = jax.nn.softmax(sl, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return out.astype(q.dtype)


def update_cache(cache: KVCache, k_new, v_new, pos) -> KVCache:
    """Insert (B, T, KVH, D) at positions [pos, pos+T) of the cache
    (a dynamic-update-slice of the sequence axis)."""
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                     (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                     (0, pos, 0, 0))
    return KVCache(k, v)


# ---------------------------------------------------------------------------
# paged KV cache: scatter writes + gather-based decode (the XLA reference
# the Pallas paged kernel must match; kernels/attention.paged_decode_attention
# is the in-kernel-gather TPU lowering)
# ---------------------------------------------------------------------------
def _row_index(cache: PagedKVCache, pidx, off):
    """Index every (token, KV head) row of layer ``cache.layer``: ``pidx``
    and ``off`` are (N, 1) page ids and in-page offsets, the result indexes
    an (N, KVH) grid of D-wide rows. Only the head dimension is left to the
    scatter's window, so the stacks keep their layout and the write lands
    in place."""
    heads = jnp.arange(cache.k.shape[1], dtype=jnp.int32)[None, :]
    return cache.layer, heads, pidx, off


def paged_update_decode(cache: PagedKVCache, k_new, v_new,
                        active: jnp.ndarray, trash_page: int) -> PagedKVCache:
    """Write one decode token per slot into its paged position.

    k_new/v_new: (B, 1, KVH, D); slot b's token lands at logical position
    ``lengths[b]`` = page ``tables[b, lengths[b]//page]`` of layer
    ``cache.layer``, offset ``lengths[b] % page``, written into the stacks
    in place (B x KVH x D values move). Inactive slots (finished/empty --
    ``active`` False) are redirected to the layer's reserved ``trash_page``
    so a retired slot can never corrupt pages the allocator has handed to
    another request, and their lengths stay frozen.
    """
    page = cache.page
    mp = cache.tables.shape[1]
    # Clamp before the gather: an inactive slot parked at full capacity
    # would otherwise index column MP (the engine only decodes slots with
    # headroom, but every slot computes its index under the static batch).
    col = jnp.minimum(cache.lengths[:, None] // page, mp - 1)
    pidx = jnp.take_along_axis(cache.tables, col, axis=1)[:, 0]
    pidx = jnp.where(active, pidx, jnp.int32(trash_page))
    off = cache.lengths % page
    idx = _row_index(cache, pidx[:, None], off[:, None])
    k = cache.k.at[idx].set(k_new[:, 0].astype(cache.k.dtype))
    v = cache.v.at[idx].set(v_new[:, 0].astype(cache.v.dtype))
    lengths = jnp.where(active, cache.lengths + 1, cache.lengths)
    return cache._replace(k=k, v=v, lengths=lengths)


def paged_update_prefill(cache: PagedKVCache, k_new, v_new,
                         pages: jnp.ndarray, start=0) -> PagedKVCache:
    """Scatter a prompt (or prompt chunk) KV into the pages allocated for it.

    k_new/v_new: (1, T, KVH, D), written in place into layer ``cache.layer``
    of the stacks; ``pages``: (MP,) page ids covering logical positions
    [0, start + T) (entries past ceil((start+T)/page) unused);
    ``start``: logical position of the chunk's first token (0 for a fresh
    whole-prompt prefill; a traced scalar for chunked-prefill continuation
    chunks). Positions past the true prompt length are bucket padding --
    they land in allocated pages but decode's length mask keeps them dead
    forever, and the next decode token overwrites the first of them.
    """
    page = cache.page
    t = k_new.shape[1]
    pos = start + jnp.arange(t)
    pidx = pages[pos // page]
    off = pos % page
    idx = _row_index(cache, pidx[:, None], off[:, None])
    k = cache.k.at[idx].set(k_new[0].astype(cache.k.dtype))
    v = cache.v.at[idx].set(v_new[0].astype(cache.v.dtype))
    return cache._replace(k=k, v=v)


def paged_decode_attention_xla(q, cache: PagedKVCache, *,
                               window: Optional[int] = None,
                               softcap: Optional[float] = None,
                               scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention over a paged cache, by explicit gather.

    q: (B, 1, H, D); ``cache.lengths`` counts the live tokens *including*
    the current one (write first, then attend). The gather indexes layer
    ``cache.layer`` of the stacked pools inside its one gather, so no
    layer's pool is sliced out first. Numerics mirror
    ``decode_attention`` exactly -- same einsums, same staging, same
    mask-then-softmax -- so a request decoded through the paged path is
    bit-identical to the dense-cache oracle (``transformer.decode_step``);
    the exact-match gate in ``tests/test_serving_families.py`` relies on
    this.
    """
    b, tq, h, d = q.shape
    _, kvh, _, page, _ = cache.k.shape
    rep = h // kvh
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    mp = cache.tables.shape[1]
    s_ctx = mp * page

    # (B, MP, KVH, page, D) -> (B, S_ctx, KVH, D) logical-position order
    def gather(pool):
        g = pool[cache.layer, :, cache.tables]
        return jnp.transpose(g, (0, 1, 3, 2, 4)).reshape(b, s_ctx, kvh, d)

    kpos = jnp.arange(s_ctx)
    pos = (cache.lengths - 1)[:, None]                  # (B, 1)
    mask = kpos[None, :] <= pos
    if window is not None:
        mask = mask & (kpos[None, :] > pos - window)

    kh = jnp.repeat(gather(cache.k), rep, axis=2)
    vh = jnp.repeat(gather(cache.v), rep, axis=2)
    sl = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * sc,
                    kh.astype(jnp.float32))
    if softcap is not None:
        sl = softcap * jnp.tanh(sl / softcap)
    sl = jnp.where(mask[:, None, None, :], sl, _NEG_INF)
    p = jax.nn.softmax(sl, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_prefill_attention_xla(q, cache: PagedKVCache, start, *,
                                window: Optional[int] = None,
                                softcap: Optional[float] = None,
                                scale: Optional[float] = None) -> jnp.ndarray:
    """Chunked-prefill attention over a paged cache, by explicit gather.

    q: (1, T, H, D), the fresh chunk's queries at logical positions
    [start, start + T); the chunk's own KV must already be scattered into
    the pool (write first, then attend -- same discipline as decode).
    ``start`` may be traced (one jit bucket serves every chunk offset).

    Numerics mirror the single-pass prefill: the gathered pages are fed to
    :func:`blockwise_attention_xla` with the same KV blocking anchored at
    position 0, so every overlapping (qpos, kpos) pair runs the identical
    online-softmax op sequence whenever both paths round to the same
    padded KV width -- here Tk = table capacity (``MP * page``), in the
    single-pass path Tk = the prompt bucket, and both clamp to a
    128-multiple, so the widths coincide exactly when both round to the
    same multiple (always at <=128-token context, the exact-match gate's
    geometry; at larger geometries with short prompts the two paths can
    pad to different widths and agree only up to float-reassociation
    noise). Trailing gathered pages past the chunk frontier are dead under
    the causal mask, exactly like the reference's pad_k region.
    """
    b, tq, h, d = q.shape
    _, kvh, _, page, _ = cache.k.shape
    mp = cache.tables.shape[1]
    s_ctx = mp * page

    def gather(pool):
        g = pool[cache.layer, :, cache.tables]
        return jnp.transpose(g, (0, 1, 3, 2, 4)).reshape(b, s_ctx, kvh, d)

    return blockwise_attention_xla(
        q, gather(cache.k), gather(cache.v), causal=True, window=window,
        softcap=softcap, scale=scale, q_offset=start, kv_len=start + tq)


# ---------------------------------------------------------------------------
# routed attention op (the tuned-schedule entry)
# ---------------------------------------------------------------------------
def _route_window(engine, window):
    """Shared routing policy for the op-layer attention entries: returns
    (window, ctx). ``engine`` may be a :class:`GemminiInstance`, a bare
    :class:`ExecutionContext`, or None (the XLA reference context). A
    static int window is normalized (0 encodes "global" -> None) and keeps
    the engine's context; a *traced* per-layer scalar (gemma-style
    local:global interleave scanned as data, 0/2^30 encoding) cannot
    parameterize a Mosaic kernel, so it demotes the context to the XLA
    backend, whose mask arithmetic handles traced scalars."""
    from repro.core import context
    ctx = context.as_context(engine)
    static_window = (window is None or isinstance(window, (int, np.integer)))
    if static_window and window is not None:
        window = int(window) or None
    if not static_window and ctx.backend != "xla":
        ctx = ctx.with_backend("xla")
    return window, ctx


def attn_op(engine, q, k, v, *,
            causal: bool = True, window=None, softcap: Optional[float] = None,
            scale: Optional[float] = None):
    """Model-zoo attention, routed through ``ctx.flash_attention`` so the
    engine's context -- not the call site -- picks the lowering, and the
    Pallas path resolves its tuned ``(block_q, block_k)`` schedule (under
    a mesh'd context: inside shard_map, at per-device shapes).
    ``transformer`` passes a static window whenever the model's layers are
    window-uniform; see :func:`_route_window` for the traced-window rule.
    """
    window, ctx = _route_window(engine, window)
    return ctx.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale)


def paged_attn_op(engine, q, cache: PagedKVCache, *, window=None,
                  softcap: Optional[float] = None,
                  scale: Optional[float] = None):
    """Paged-decode twin of :func:`attn_op`: routes through
    ``ctx.paged_attention`` (in-kernel gather on pallas/interpret engines,
    explicit gather on xla); a traced per-layer window falls back to the
    gather path, whose masking handles traced scalars."""
    window, ctx = _route_window(engine, window)
    return ctx.paged_attention(q, cache.k, cache.v, cache.tables,
                               cache.lengths, cache.layer, window=window,
                               softcap=softcap, scale=scale)


def paged_prefill_attn_op(engine, q, cache: PagedKVCache, start, *,
                          window=None, softcap: Optional[float] = None,
                          scale: Optional[float] = None,
                          kv_pages: Optional[int] = None):
    """Chunked-prefill twin of :func:`paged_attn_op`: the fresh chunk's
    queries attend cache pages + the chunk itself through
    ``ctx.paged_prefill_attention`` (in-kernel gather on pallas/interpret
    engines, explicit gather on xla); a traced per-layer window falls back
    to the gather path, whose masking handles traced scalars. ``kv_pages``
    is the engine's STATIC admission-time bound on live table entries
    (dead-key MAC elision for short prompts; see
    ``ops.paged_prefill_attention_impl``)."""
    window, ctx = _route_window(engine, window)
    return ctx.paged_prefill_attention(
        q, cache.k, cache.v, cache.tables[0], start, cache.layer,
        window=window, softcap=softcap, scale=scale, kv_pages=kv_pages)


# ---------------------------------------------------------------------------
# full attention block
# ---------------------------------------------------------------------------
def attn_apply(engine: GemminiInstance, p: Params, x: jnp.ndarray, *,
               n_heads: int, n_kv: int, head_dim: int,
               positions: jnp.ndarray,
               window: Optional[int] = None,
               softcap: Optional[float] = None,
               rope_base: float = 10000.0,
               query_scale: Optional[float] = None,
               cache: Optional[KVCache] = None,
               cache_pos: Optional[jnp.ndarray] = None,
               ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Self-attention with optional KV cache (decode when x has T==1)."""
    b, t, _ = x.shape
    q, k, v = _qkv(engine, p, x, n_heads, n_kv, head_dim)
    q = layers.rope(q, positions, base=rope_base)
    k = layers.rope(k, positions, base=rope_base)
    if cache is not None:
        cache = update_cache(cache, k, v, cache_pos)
        if t == 1:
            o = decode_attention(q, cache, cache_pos, window=window,
                                 softcap=softcap, scale=query_scale)
        else:  # chunked prefill into cache
            o = attn_op(engine, q, cache.k[:, :], cache.v[:, :],
                        causal=True, window=window, softcap=softcap,
                        scale=query_scale)
    else:
        o = attn_op(engine, q, k, v, causal=True, window=window,
                    softcap=softcap, scale=query_scale)
    o = o.reshape(b, t, n_heads * head_dim)
    return layers.project(engine, o, p["wo"]), cache
