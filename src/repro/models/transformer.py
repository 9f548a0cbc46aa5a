"""Decoder stack covering all 10 assigned architectures.

One *homogeneous* block definition per family (dense / moe / ssm / hybrid),
scanned over layers with stacked parameters so 60-layer models lower to a
single compiled block body (compile-time tractability for the 512-device
dry-run). Per-layer heterogeneity (gemma local:global interleave, per-layer
rope bases) is expressed as *scanned data* (traced per-layer window size /
rope base arrays), not as distinct block bodies.

Multimodal frontends are stubs per the assignment: ``extra_embeds`` carries
precomputed patch (VLM) or frame (audio) embeddings, concatenated before the
first block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.generator import GemminiInstance
from repro.models import attention as attn
from repro.models import layers, moe, ssm

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# model configuration
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    activation: str = "silu"
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    local_window: Optional[int] = None   # sliding window for "local" layers
    global_period: int = 0               # every Nth layer is global (0 = all global)
    rope_base: float = 10000.0
    rope_base_local: Optional[float] = None
    post_norms: bool = False             # gemma2/3 post-block norms
    qk_norm: bool = False                # gemma3
    embed_scale: bool = False            # gemma: embeddings * sqrt(d)
    tie_embeddings: bool = True
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    router_weights_before: bool = False  # llama4 style
    capacity_factor: float = 1.25
    expert_padding: int = 16             # pad experts to the EP degree
    # SSM
    d_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    d_conv: int = 4
    ssm_chunk: int = 256
    # multimodal stubs
    modality: str = "none"               # none | vlm | audio
    n_codebooks: int = 1                 # musicgen
    n_meta_tokens: int = 0               # hymba
    dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def has_attn(self) -> bool:
        return self.family in ("dense", "moe", "hybrid")

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS roofline terms)."""
        d, l = self.d_model, self.n_layers
        n = self.vocab * d * self.n_codebooks          # embed
        if not self.tie_embeddings or self.n_codebooks > 1:
            n += self.vocab * d * self.n_codebooks     # unembed heads
        per_layer = 0
        if self.has_attn:
            per_layer += d * (self.n_heads + 2 * self.n_kv_heads) * \
                self.head_dim + self.n_heads * self.head_dim * d
        if self.has_ssm:
            in_dim = 2 * self.d_inner + 2 * self.ssm_groups * self.d_state \
                + self.n_ssm_heads
            per_layer += d * in_dim + self.d_inner * d
        if self.family == "moe":
            e = self.n_experts
            per_layer += d * e                                   # router
            per_layer += 3 * d * self.moe_d_ff * e               # experts
            if self.n_shared_experts:
                per_layer += 3 * d * self.moe_d_ff * self.n_shared_experts
        elif self.family in ("dense", "hybrid") and self.d_ff:
            per_layer += 3 * d * self.d_ff
        return n + l * per_layer

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts)."""
        if self.family != "moe":
            return self.param_count()
        d, l, e = self.d_model, self.n_layers, self.n_experts
        full = self.param_count()
        inactive = l * 3 * d * self.moe_d_ff * (e - self.top_k)
        return full - inactive


def layer_windows(cfg: ModelConfig, seq_hint: int) -> np.ndarray:
    """Per-layer sliding-window sizes; 0 encodes 'global' (full attention)."""
    win = np.zeros((cfg.n_layers,), np.int32)
    if cfg.local_window:
        for i in range(cfg.n_layers):
            is_global = (cfg.global_period > 0 and
                         (i + 1) % cfg.global_period == 0)
            win[i] = 0 if is_global else cfg.local_window
    return win


def uniform_window(win_np: np.ndarray) -> Optional[int]:
    """The single static window shared by every layer (0 = global), or None
    when layers disagree (gemma-style local:global interleave). A static
    window lets the layer scan route attention to the Pallas kernel (and
    its tuned schedule) on pallas/interpret engines; mixed-window models
    scan the window as traced data and keep the XLA path."""
    vals = {int(w) for w in win_np}
    return vals.pop() if len(vals) == 1 else None


def layer_rope_bases(cfg: ModelConfig) -> np.ndarray:
    base = np.full((cfg.n_layers,), cfg.rope_base, np.float32)
    if cfg.rope_base_local is not None and cfg.local_window:
        for i in range(cfg.n_layers):
            is_global = (cfg.global_period > 0 and
                         (i + 1) % cfg.global_period == 0)
            if not is_global:
                base[i] = cfg.rope_base_local
    return base


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------
def _block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {"ln1": layers.rmsnorm_init(cfg.d_model)}
    if cfg.has_attn:
        p["attn"] = attn.attn_init(ks[0], cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim,
                                   qkv_bias=cfg.qkv_bias, dtype=cfg.dtype)
        if cfg.qk_norm:
            p["qnorm"] = layers.rmsnorm_init(cfg.head_dim)
            p["knorm"] = layers.rmsnorm_init(cfg.head_dim)
    if cfg.has_ssm:
        p["mamba"] = ssm.mamba2_init(
            ks[1], cfg.d_model, d_inner=cfg.d_inner,
            n_heads=cfg.n_ssm_heads, d_state=cfg.d_state,
            n_groups=cfg.ssm_groups, d_conv=cfg.d_conv, dtype=cfg.dtype)
    if cfg.family == "moe":
        p["ln2"] = layers.rmsnorm_init(cfg.d_model)
        p["moe"] = moe.moe_init(ks[2], cfg.d_model, cfg.moe_d_ff,
                                cfg.n_experts, ep=cfg.expert_padding,
                                n_shared=cfg.n_shared_experts,
                                dtype=cfg.dtype)
    elif cfg.d_ff and cfg.family != "ssm":
        p["ln2"] = layers.rmsnorm_init(cfg.d_model)
        p["mlp"] = layers.mlp_init(ks[3], cfg.d_model, cfg.d_ff,
                                   dtype=cfg.dtype)
    if cfg.post_norms:
        p["post_ln1"] = layers.rmsnorm_init(cfg.d_model)
        if "ln2" in p:
            p["post_ln2"] = layers.rmsnorm_init(cfg.d_model)
    if cfg.family == "hybrid":
        # per-branch output norms before averaging (hymba)
        p["attn_out_norm"] = layers.rmsnorm_init(cfg.d_model)
        p["ssm_out_norm"] = layers.rmsnorm_init(cfg.d_model)
    return p


# Param names that are engine-backed (d_in, d_out) projection weights.
# MoE expert stacks (4D: layers x experts x d x d_ff) are excluded: their
# per-expert GEMMs run through einsum in models/moe.py, not through
# ctx.gemm, so they never resolve a tile plan. (The MoE router and shared
# MLP do route through the engine and are covered.)
_PROJ_KEYS = frozenset({"wq", "wk", "wv", "wo", "wi", "wg", "router",
                        "in_proj", "out_proj", "unembed", "heads"})
# Of those, the ones inside a block: stacked (L, d_in, d_out) in
# params["blocks"], read by ``layers.project`` alone.
_STACKED_PROJ_KEYS = _PROJ_KEYS - {"unembed", "heads"}


def _names(path) -> tuple:
    """The dict keys along a pytree path."""
    return tuple(p.key for p in path
                 if isinstance(p, jax.tree_util.DictKey))


def model_gemm_shapes(cfg: ModelConfig, batch: int, seq: int, *,
                      include_decode: bool = True) -> list:
    """Every (M, N, K, has_bias) GEMM shape the model's projections run.

    Walked from the parameter tree under ``jax.eval_shape`` (no allocation):
    each projection weight's trailing (d_in, d_out) becomes a
    (batch*seq, d_out, d_in) prefill/train GEMM, plus the (batch, d_out,
    d_in) single-token decode GEMM. ``has_bias`` is detected from a sibling
    bias leaf (``wq`` -> ``bq``): biased projections ride the engine's
    native D input (``layers.project``), and the tuner fingerprints them
    separately, so the warm pass must resolve them with the flag or it
    populates entries the request path never hits. Used by
    ``repro.tune.warm_model_plans`` to pre-tune a whole model's schedule
    before the first request arrives.
    """
    import functools
    shapes = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    ms = [batch * seq] + ([batch] if include_decode else [])
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]

    # Leaf names present under each parent dict, to detect sibling biases.
    siblings: dict = {}
    for path, _ in leaves:
        names = _names(path)
        if names:
            siblings.setdefault(names[:-1], set()).add(names[-1])

    out, seen = [], set()
    for path, leaf in leaves:
        if len(leaf.shape) < 2:
            continue
        names = _names(path)
        name = names[-1] if names else ""
        if "moe" in names and name in ("wi", "wg", "wo"):
            continue                      # einsum expert GEMMs, not engine
        if name in _PROJ_KEYS:
            k_in, n_out = leaf.shape[-2], leaf.shape[-1]
        elif name == "embed" and cfg.tie_embeddings and cfg.n_codebooks == 1:
            k_in, n_out = leaf.shape[-1], leaf.shape[-2]   # unembed: table.T
        else:
            continue
        has_bias = (name.startswith("w")
                    and "b" + name[1:] in siblings.get(names[:-1], ()))
        for m in ms:
            t = (int(m), int(n_out), int(k_in), bool(has_bias))
            if t not in seen:
                seen.add(t)
                out.append(t)
    return out


def model_attention_shapes(cfg: ModelConfig, batch: int, seq: int) -> list:
    """Every (B, Tq, Tk, H, KVH, D, causal, window) flash-attention shape
    the model runs at this (batch, seq): one per distinct per-layer window
    (gemma-style local:global interleaving collapses to two shapes). Used
    by ``repro.tune.warm_model_plans`` so attention schedules resolve from
    the cache on the request path."""
    if not cfg.has_attn:
        return []
    out = []
    for w in sorted({int(w) for w in layer_windows(cfg, seq)}):
        out.append((batch, seq, seq, cfg.n_heads, cfg.n_kv_heads,
                    cfg.head_dim, True, None if w == 0 else w))
    return out


def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4 + cfg.n_layers)
    if cfg.n_codebooks > 1:
        embed = jnp.stack([layers.embed_init(k, cfg.vocab, cfg.d_model,
                                             dtype=cfg.dtype)
                           for k in jax.random.split(ks[0], cfg.n_codebooks)])
    else:
        embed = layers.embed_init(ks[0], cfg.vocab, cfg.d_model,
                                  dtype=cfg.dtype)
    # stacked per-layer params, built stacked (vmap over the layer keys):
    # a list of per-layer trees plus their stack would hold the blocks
    # twice, which at published widths does not fit one chip
    blocks = jax.vmap(lambda k: _block_init(k, cfg))(ks[4:])
    p: Params = {"embed": embed, "blocks": blocks,
                 "final_norm": layers.rmsnorm_init(cfg.d_model)}
    if cfg.n_codebooks > 1:
        p["heads"] = jnp.stack([layers.dense_init(k, cfg.d_model, cfg.vocab,
                                                  dtype=cfg.dtype)
                                for k in jax.random.split(ks[1],
                                                          cfg.n_codebooks)])
    elif not cfg.tie_embeddings:
        p["unembed"] = layers.dense_init(ks[1], cfg.d_model, cfg.vocab,
                                         dtype=cfg.dtype)
    if cfg.n_meta_tokens:
        p["meta_tokens"] = (jax.random.normal(
            ks[2], (cfg.n_meta_tokens, cfg.d_model), jnp.float32) * 0.02
        ).astype(cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# block forward (shared by train/prefill and decode)
# ---------------------------------------------------------------------------
def _maybe_qknorm(cfg, bp, q, k):
    if cfg.qk_norm:
        q = layers.rmsnorm(q, bp["qnorm"])
        k = layers.rmsnorm(k, bp["knorm"])
    return q, k


def _attn_branch(engine, cfg, bp, h, positions, window, rope_base,
                 cache=None, cache_pos=None, window_static=None,
                 prefill_start=None, kv_pages=None):
    """window: traced scalar, 0 = global; window_static: the same value as
    a python int when the model is window-uniform (None = unavailable, use
    the traced scalar). Returns (out, new_cache). ``cache`` may be a dense
    :class:`attn.KVCache` (static-batch serving) or a paged
    :class:`attn.PagedKVCache` (the continuous-batching engine).
    ``prefill_start``: traced scalar cache position of a chunked-prefill
    continuation chunk's first token (None = not a continuation chunk);
    selects the scatter-at-offset + cache-and-chunk gather attention path.
    ``kv_pages``: static bound on live block-table entries for that path
    (the serving engine's admission-time prompt footprint)."""
    b, t, _ = h.shape
    p = bp["attn"]
    q = layers.project(engine, h, p["wq"], p.get("bq")).reshape(
        b, t, cfg.n_heads, cfg.head_dim)
    k = layers.project(engine, h, p["wk"], p.get("bk")).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    v = layers.project(engine, h, p["wv"], p.get("bv")).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    q, k = _maybe_qknorm(cfg, bp, q, k)
    q = layers.rope(q, positions, base=rope_base)
    k = layers.rope(k, positions, base=rope_base)

    # encode "global" as window > any position: mask kpos > qpos - window
    eff_window = jnp.where(window > 0, window, jnp.int32(2 ** 30))
    win_arg = window_static if window_static is not None else eff_window
    if isinstance(cache, attn.PagedKVCache):
        # The continuation-chunk test must PRECEDE the t == 1 decode test:
        # a final chunk can legally be one token long (recurrent families
        # never pad, so total % chunk == 1 happens), and routing it to the
        # decode branch would read the chunk cache's unset active/trash.
        if prefill_start is not None:
            # chunked-prefill continuation: scatter the chunk's KV at its
            # offset, then attend cache pages + the fresh chunk through the
            # block-table gather path (write first, then attend).
            cache = attn.paged_update_prefill(cache, k, v, cache.tables[0],
                                              start=prefill_start)
            o = attn.paged_prefill_attn_op(engine, q, cache, prefill_start,
                                           window=win_arg,
                                           softcap=cfg.attn_softcap,
                                           kv_pages=kv_pages)
        elif t == 1:
            cache = attn.paged_update_decode(cache, k, v, cache.active,
                                             cache.trash)
            o = attn.paged_attn_op(engine, q, cache, window=win_arg,
                                   softcap=cfg.attn_softcap)
        else:
            # fresh-request prefill: the prompt attends only itself, so the
            # pool is write-only here (scatter into the allocated pages).
            cache = attn.paged_update_prefill(cache, k, v, cache.tables[0])
            o = attn.attn_op(engine, q, k, v, causal=True, window=win_arg,
                             softcap=cfg.attn_softcap)
    elif cache is not None:
        cache = attn.update_cache(cache, k, v, cache_pos)
        if t == 1:
            o = attn.decode_attention(q, cache, cache_pos,
                                      window=eff_window,
                                      softcap=cfg.attn_softcap)
        else:
            # prefill from position 0: attend only the t written positions
            # (the cache tail beyond t is unwritten zeros, and blockwise
            # attention right-aligns queries against the key length).
            o = attn.attn_op(engine, q, cache.k[:, :t], cache.v[:, :t],
                             causal=True, window=win_arg,
                             softcap=cfg.attn_softcap)
    else:
        o = attn.attn_op(engine, q, k, v, causal=True, window=win_arg,
                         softcap=cfg.attn_softcap)
    o = o.reshape(b, t, cfg.n_heads * cfg.head_dim)
    return layers.project(engine, o, p["wo"]), cache


def _block_apply(engine, cfg: ModelConfig, bp: Params, h: jnp.ndarray,
                 positions, window, rope_base,
                 kv_cache=None, ssm_cache=None, cache_pos=None,
                 window_static=None, prefill_start=None, kv_pages=None):
    """One decoder block. Returns (h, kv_cache, ssm_cache)."""
    x = layers.rmsnorm(h, bp["ln1"])
    outs = []
    if cfg.has_attn:
        a_out, kv_cache = _attn_branch(engine, cfg, bp, x, positions, window,
                                       rope_base, kv_cache, cache_pos,
                                       window_static=window_static,
                                       prefill_start=prefill_start,
                                       kv_pages=kv_pages)
        outs.append(("attn", a_out))
    if cfg.has_ssm:
        s_out, ssm_cache = ssm.mamba2_apply(
            engine, bp["mamba"], x, d_inner=cfg.d_inner,
            n_heads=cfg.n_ssm_heads, d_state=cfg.d_state,
            n_groups=cfg.ssm_groups, chunk=cfg.ssm_chunk, cache=ssm_cache)
        outs.append(("ssm", s_out))
    if cfg.family == "hybrid":
        a = layers.rmsnorm(outs[0][1], bp["attn_out_norm"])
        s = layers.rmsnorm(outs[1][1], bp["ssm_out_norm"])
        mixed = 0.5 * (a.astype(jnp.float32) + s.astype(jnp.float32))
        mixed = mixed.astype(h.dtype)
    else:
        mixed = outs[0][1]
    if cfg.post_norms:
        mixed = layers.rmsnorm(mixed, bp["post_ln1"])
    h = h + mixed

    if "moe" in bp:
        x2 = layers.rmsnorm(h, bp["ln2"])
        serving = kv_cache is not None or ssm_cache is not None
        f = moe.moe_apply(engine, bp["moe"], x2, n_experts=cfg.n_experts,
                          top_k=cfg.top_k,
                          capacity_factor=cfg.capacity_factor,
                          activation=cfg.activation,
                          router_weights_before=cfg.router_weights_before,
                          dropless=serving)
        if cfg.post_norms:
            f = layers.rmsnorm(f, bp["post_ln2"])
        h = h + f
    elif "mlp" in bp:
        x2 = layers.rmsnorm(h, bp["ln2"])
        f = layers.mlp_apply(engine, bp["mlp"], x2, activation=cfg.activation)
        if cfg.post_norms:
            f = layers.rmsnorm(f, bp["post_ln2"])
        h = h + f
    return h, kv_cache, ssm_cache


# ---------------------------------------------------------------------------
# embedding frontends (incl. multimodal stubs)
# ---------------------------------------------------------------------------
def embed_inputs(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                 extra_embeds: Optional[jnp.ndarray] = None, *,
                 with_meta: bool = True) -> jnp.ndarray:
    """tokens: (B, T) or (B, T, n_q) for audio. extra_embeds: (B, Ti, D)
    precomputed frontend embeddings (VLM patches / audio conditioning),
    prepended to the token embeddings. ``with_meta=False`` skips the
    hymba meta-token prefix -- chunked prefill prepends it only on the
    first chunk (the meta tokens live at cache positions [0, n_meta))."""
    if cfg.n_codebooks > 1:
        # musicgen: sum the per-codebook embeddings
        h = sum(layers.embed_apply(params["embed"][i], tokens[..., i])
                for i in range(cfg.n_codebooks))
    else:
        h = layers.embed_apply(params["embed"], tokens,
                               scale_by_sqrt_dim=cfg.embed_scale)
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=1)
    if cfg.n_meta_tokens and with_meta:
        b = h.shape[0]
        meta = jnp.broadcast_to(params["meta_tokens"][None],
                                (b, cfg.n_meta_tokens, cfg.d_model))
        h = jnp.concatenate([meta.astype(h.dtype), h], axis=1)
    return h


def unembed(engine, cfg: ModelConfig, params: Params,
            h: jnp.ndarray) -> jnp.ndarray:
    h = layers.rmsnorm(h, params["final_norm"])
    if cfg.n_codebooks > 1:
        logits = jnp.stack(
            [layers.project(engine, h, params["heads"][i])
             for i in range(cfg.n_codebooks)], axis=-2)  # (B,T,n_q,V)
        return logits.astype(jnp.float32)
    table = params["embed"] if cfg.tie_embeddings else None
    if table is not None:
        return layers.unembed_apply(engine, table, h,
                                    softcap=cfg.final_softcap)
    logits = layers.project(engine, h, params["unembed"]).astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------
def _constrain(x, sharding):
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def forward(engine: GemminiInstance, params: Params, cfg: ModelConfig,
            tokens: jnp.ndarray,
            extra_embeds: Optional[jnp.ndarray] = None, *,
            remat: bool = False,
            residual_sharding=None,
            logits_sharding=None) -> jnp.ndarray:
    """remat: rematerialize each block in backward (train memory policy).
    residual_sharding: NamedSharding for the (B, T, D) layer-scan carry
    (sequence-parallel storage); logits_sharding: vocab-sharded logits."""
    h = embed_inputs(cfg, params, tokens, extra_embeds)
    b, t, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    win_np = layer_windows(cfg, t)
    windows = jnp.asarray(win_np)
    bases = jnp.asarray(layer_rope_bases(cfg))
    h = _constrain(h, residual_sharding)

    def body(h, xs):
        bp, win, base = xs
        # No window_static here: forward() is the TRAIN path (loss_fn
        # differentiates through it) and the Pallas flash kernel has no
        # VJP, so attention must stay on the differentiable XLA route on
        # every backend. The inference paths (prefill_into_cache /
        # paged_prefill) pass the static window and get the kernel.
        h, _, _ = _block_apply(engine, cfg, bp, h, positions, win, base)
        return _constrain(h, residual_sharding), None

    if remat:
        from repro.core import flags
        pol = flags.get("remat_policy")
        if pol == "dots":
            # save MXU outputs, recompute elementwise: spends VMEM/HBM
            # residency to avoid re-running every projection (and its TP
            # collectives) in the backward pass
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.
                dots_with_no_batch_dims_saveable)
        elif pol == "none":
            pass                     # save everything (no recompute)
        else:                        # "full": the minimal-residency baseline
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, (params["blocks"], windows, bases))
    logits = unembed(engine, cfg, params, h)
    return _constrain(logits, logits_sharding)


def loss_fn(engine, params, cfg: ModelConfig, tokens, labels,
            extra_embeds=None, **fwd_kw) -> jnp.ndarray:
    """Next-token cross-entropy; labels == -100 are masked."""
    logits = forward(engine, params, cfg, tokens, extra_embeds, **fwd_kw)
    if extra_embeds is not None:       # prefix positions carry no loss
        logits = logits[:, extra_embeds.shape[1]:]
    if cfg.n_meta_tokens:
        logits = logits[:, cfg.n_meta_tokens:]
    if cfg.n_codebooks > 1:
        logits = logits[:, :-1]                       # (B,T-1,n_q,V)
        tgt = labels[:, 1:]                           # (B,T-1,n_q)
    else:
        logits = logits[:, :-1]
        tgt = labels[:, 1:]
    mask = (tgt >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[..., None],
                             axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# dense-cache decode: the one-request oracle. One contiguous (B, S) cache
# and one shared position; the paged engine's greedy tokens are checked
# against it token for token (tests/test_serving*.py), and the dry run's
# decode shapes lower it (launch/steps.make_serve_step). The engine itself
# runs the paged path below.
# ---------------------------------------------------------------------------
class DecodeState(NamedTuple):
    kv_k: Optional[jnp.ndarray]       # (L, B, S, KVH, D) or None
    kv_v: Optional[jnp.ndarray]
    conv: Optional[jnp.ndarray]       # (L, B, K-1, conv_dim) or None
    ssm: Optional[jnp.ndarray]        # (L, B, H, N, P) or None
    pos: jnp.ndarray                  # scalar int32: next write position


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16) -> DecodeState:
    kv_k = kv_v = conv = st = None
    if cfg.has_attn:
        shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        kv_k = jnp.zeros(shape, dtype)
        kv_v = jnp.zeros(shape, dtype)
    if cfg.has_ssm:
        conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state
        conv = jnp.zeros((cfg.n_layers, batch, cfg.d_conv - 1, conv_dim),
                         dtype)
        st = jnp.zeros((cfg.n_layers, batch, cfg.n_ssm_heads, cfg.d_state,
                        cfg.ssm_head_dim), jnp.float32)
    return DecodeState(kv_k, kv_v, conv, st,
                       jnp.zeros((), jnp.int32) + (max_seq - 1))


def prefill_into_cache(engine: GemminiInstance, params: Params,
                       cfg: ModelConfig, tokens: jnp.ndarray,
                       state: DecodeState,
                       extra_embeds: Optional[jnp.ndarray] = None
                       ) -> Tuple[jnp.ndarray, DecodeState]:
    """The oracle's prefill: forward over the prompt writing the dense
    KV/SSM caches at positions [0, P).

    tokens: (B, P) [or (B, P, n_q)]. Returns (logits (B, P', V), state with
    ``pos`` = number of cached positions = the next write position).
    """
    h = embed_inputs(cfg, params, tokens, extra_embeds)
    b, t, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    win_np = layer_windows(cfg, t)
    windows = jnp.asarray(win_np)
    static_win = uniform_window(win_np)
    bases = jnp.asarray(layer_rope_bases(cfg))
    write_pos = jnp.zeros((), jnp.int32)

    def body(h, xs):
        bp, win, base, kv_k, kv_v, conv, st = xs
        kvc = attn.KVCache(kv_k, kv_v) if kv_k is not None else None
        # state=None: a FRESH whole-prompt prefill (init_decode_state's
        # zeros carry no history) -- routes the SSD to the fused kernel
        # on pallas/interpret engines (see ssm.SSMCache).
        ssc = ssm.SSMCache(conv, None) if conv is not None else None
        h, kvc, ssc = _block_apply(engine, cfg, bp, h, positions, win, base,
                                   kv_cache=kvc, ssm_cache=ssc,
                                   cache_pos=write_pos,
                                   window_static=static_win)
        new = (kvc.k if kvc else None, kvc.v if kvc else None,
               ssc.conv if ssc else None, ssc.state if ssc else None)
        return h, new

    xs = (params["blocks"], windows, bases, state.kv_k, state.kv_v,
          state.conv, state.ssm)
    h, caches = jax.lax.scan(body, h, xs)
    kv_k, kv_v, conv, st = caches
    logits = unembed(engine, cfg, params, h)
    return logits, DecodeState(kv_k, kv_v, conv, st,
                               jnp.asarray(t, jnp.int32))


def decode_step(engine: GemminiInstance, params: Params, cfg: ModelConfig,
                tokens: jnp.ndarray, state: DecodeState
                ) -> Tuple[jnp.ndarray, DecodeState]:
    """The oracle's decode step: tokens (B, 1) [or (B, 1, n_q)] with a dense
    KV/SSM cache of ``max_seq``; returns logits for the new token and the
    updated state."""
    if cfg.n_codebooks > 1:
        h = sum(layers.embed_apply(params["embed"][i], tokens[..., i])
                for i in range(cfg.n_codebooks))
    else:
        h = layers.embed_apply(params["embed"], tokens,
                               scale_by_sqrt_dim=cfg.embed_scale)
    b = h.shape[0]
    pos = state.pos
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    windows = jnp.asarray(layer_windows(cfg, 0))
    bases = jnp.asarray(layer_rope_bases(cfg))

    def body(h, xs):
        bp, win, base, kv_k, kv_v, conv, st = xs
        kvc = attn.KVCache(kv_k, kv_v) if kv_k is not None else None
        ssc = ssm.SSMCache(conv, st) if conv is not None else None
        h, kvc, ssc = _block_apply(engine, cfg, bp, h, positions, win, base,
                                   kv_cache=kvc, ssm_cache=ssc,
                                   cache_pos=pos)
        new = (kvc.k if kvc else None, kvc.v if kvc else None,
               ssc.conv if ssc else None, ssc.state if ssc else None)
        return h, new

    xs = (params["blocks"], windows, bases, state.kv_k, state.kv_v,
          state.conv, state.ssm)
    h, caches = jax.lax.scan(body, h, xs)
    kv_k, kv_v, conv, st = caches
    logits = unembed(engine, cfg, params, h)
    return logits, DecodeState(kv_k, kv_v, conv, st, pos + 1)


# ---------------------------------------------------------------------------
# paged decode (the continuous-batching serving engine's substrate)
# ---------------------------------------------------------------------------
class PagedDecodeState(NamedTuple):
    """Decode-slot state over *paged* KV pools.

    Unlike :class:`DecodeState` (one contiguous (B, S) cache, one shared
    scalar position), slots here are independent requests at independent
    positions: page pools shared by every slot, every layer's stacked in
    one array (the paged steps carry the stacks through the layer scan and
    index a layer's pages by layer index, see ``_scan_paged``), per-slot
    block tables mapping logical positions to pool pages, and per-slot
    lengths. The last pool page (id NP) of every layer is the reserved
    trash page retired slots spill to; the allocator only ever hands out
    ids [0, NP).
    """

    kv_k: Optional[jnp.ndarray]       # (L, KVH, NP + 1, page, D) or None
    kv_v: Optional[jnp.ndarray]
    conv: Optional[jnp.ndarray]       # (L, slots, K-1, conv_dim) or None
    ssm: Optional[jnp.ndarray]        # (L, slots, H, N, P) or None
    tables: jnp.ndarray               # (slots, MP) int32 page ids
    lengths: jnp.ndarray              # (slots,) int32 cached tokens per slot


def init_paged_state(cfg: ModelConfig, slots: int, n_pages: int,
                     page_size: int, max_pages: int,
                     dtype=jnp.bfloat16) -> PagedDecodeState:
    kv_k = kv_v = conv = st = None
    if cfg.has_attn:
        shape = (cfg.n_layers, cfg.n_kv_heads, n_pages + 1, page_size,
                 cfg.head_dim)
        kv_k = jnp.zeros(shape, dtype)
        kv_v = jnp.zeros(shape, dtype)
    if cfg.has_ssm:
        conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state
        conv = jnp.zeros((cfg.n_layers, slots, cfg.d_conv - 1, conv_dim),
                         dtype)
        st = jnp.zeros((cfg.n_layers, slots, cfg.n_ssm_heads, cfg.d_state,
                        cfg.ssm_head_dim), jnp.float32)
    return PagedDecodeState(kv_k, kv_v, conv, st,
                            jnp.zeros((slots, max_pages), jnp.int32),
                            jnp.zeros((slots,), jnp.int32))


def _scan_paged(cfg: ModelConfig, params: Params, h: jnp.ndarray,
                state: PagedDecodeState, windows, bases, cache, block
                ) -> Tuple[jnp.ndarray, PagedDecodeState]:
    """Scan the decoder blocks over paged state.

    The stacked KV pools ride the scan's carry whole: each layer writes
    its tokens into them in place and its attention reads its own pages
    straight from the stack, by layer index. No layer's pool is sliced
    out or stacked back, which would copy the arena every layer. The
    projection matrices stay in their stacks too: the body closes over
    them and hands each block :class:`layers.StackedWeight` leaves, which
    the engine GEMM reads by layer index. Norms, biases and the SSM slot
    state (small, per layer or per slot) ride ``xs``/``ys``.

    ``cache(kv_k, kv_v, li)`` builds layer ``li``'s
    :class:`attn.PagedKVCache`; ``block(h, bp, win, base, kvc, conv, st)``
    runs one block and returns ``(h, kvc, conv, st)``.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params["blocks"])
    in_stack = [_names(path)[-1] in _STACKED_PROJ_KEYS and leaf.ndim == 3
                for path, leaf in leaves]

    def body(carry, xs):
        h, kv_k, kv_v = carry
        sliced, win, base, li, conv, st = xs
        rest = iter(sliced)
        bp = treedef.unflatten([
            layers.StackedWeight(leaf, li) if stacked else next(rest)
            for (_, leaf), stacked in zip(leaves, in_stack)])
        kvc = cache(kv_k, kv_v, li) if kv_k is not None else None
        h, kvc, conv, st = block(h, bp, win, base, kvc, conv, st)
        kv_k, kv_v = (kvc.k, kvc.v) if kvc is not None else (None, None)
        return (h, kv_k, kv_v), (conv, st)

    sliced = [leaf for (_, leaf), stacked in zip(leaves, in_stack)
              if not stacked]
    xs = (sliced, windows, bases,
          jnp.arange(cfg.n_layers, dtype=jnp.int32), state.conv, state.ssm)
    (h, kv_k, kv_v), (conv, st) = jax.lax.scan(
        body, (h, state.kv_k, state.kv_v), xs)
    return h, state._replace(kv_k=kv_k, kv_v=kv_v, conv=conv, ssm=st)


def paged_prefill(engine: GemminiInstance, params: Params, cfg: ModelConfig,
                  tokens: jnp.ndarray, state: PagedDecodeState,
                  slot: jnp.ndarray, pages: jnp.ndarray, *,
                  page_size: int, with_logits: bool = True
                  ) -> Tuple[Optional[jnp.ndarray], PagedDecodeState]:
    """Prefill ONE fresh request into the paged pools.

    ``with_logits=False`` skips the unembed projection (used when this is
    the FIRST chunk of a multi-chunk prefill: nothing samples until the
    last chunk).

    tokens: (1, P) [or (1, P, n_q)], P bucket-padded by the engine; slot:
    scalar int32 decode slot; pages: (MP,) int32 pages allocated for the
    request (entries past ceil(T'/page) unused, T' = P + meta tokens).
    Returns (logits (1, T', V), state with the pools and the slot's SSM
    caches written). The caller owns the host-side table/length update
    (``lengths[slot] = true_len + meta``, ``tables[slot] = pages``) --
    bucket-padding positions land in the allocated pages but stay dead
    under the length mask, and the first decode token overwrites the first
    of them. SSM slot caches start from zeros (a fresh request must not
    inherit a retired tenant's recurrent state).
    """
    h = embed_inputs(cfg, params, tokens)
    b, t, _ = h.shape                                  # b == 1
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    win_np = layer_windows(cfg, t)
    windows = jnp.asarray(win_np)
    static_win = uniform_window(win_np)
    bases = jnp.asarray(layer_rope_bases(cfg))
    zero_len = jnp.zeros((1,), jnp.int32)

    def block(h, bp, win, base, kvc, conv, st):
        ssc = None
        if conv is not None:
            # Fresh request: conv state zeroed, recurrent state spelled
            # None (fresh-prefill marker -- a retired tenant's state must
            # not leak in, and the SSD kernel path starts from zeros).
            c1 = jnp.zeros_like(jax.lax.dynamic_slice_in_dim(conv, slot, 1, 0))
            ssc = ssm.SSMCache(c1, None)
        h, kvc, ssc = _block_apply(engine, cfg, bp, h, positions, win, base,
                                   kv_cache=kvc, ssm_cache=ssc,
                                   window_static=static_win)
        if ssc is not None:
            conv = jax.lax.dynamic_update_slice_in_dim(
                conv, ssc.conv.astype(conv.dtype), slot, 0)
            st = jax.lax.dynamic_update_slice_in_dim(
                st, ssc.state.astype(st.dtype), slot, 0)
        return h, kvc, conv, st

    h, state = _scan_paged(
        cfg, params, h, state, windows, bases,
        lambda k, v, li: attn.PagedKVCache(k, v, pages[None], zero_len,
                                           page_size, li), block)
    logits = unembed(engine, cfg, params, h) if with_logits else None
    return logits, state


def paged_prefill_chunk(engine: GemminiInstance, params: Params,
                        cfg: ModelConfig, tokens: jnp.ndarray,
                        state: PagedDecodeState, slot: jnp.ndarray,
                        pages: jnp.ndarray, start: jnp.ndarray, *,
                        page_size: int, with_logits: bool = True,
                        kv_pages: Optional[int] = None
                        ) -> Tuple[Optional[jnp.ndarray], PagedDecodeState]:
    """Prefill a CONTINUATION chunk of a partially-prefilled request.

    tokens: (1, Tc) [or (1, Tc, n_q)] prompt tokens landing at cache
    positions [start, start + Tc); start: *traced* scalar int32, so one
    compile bucket serves every chunk offset of a given chunk length (the
    first chunk -- which prepends meta tokens and attends only itself --
    goes through :func:`paged_prefill`); pages: (MP,) int32, the slot's
    full block table so far (the chunk's own pages included).

    Differences from the fresh-prefill path, all chunk-resume semantics:
    positions and rope run at [start, start+Tc); attention scatters at the
    offset and then attends cache pages + the fresh chunk via the
    block-table gather (``ops.paged_prefill_attention``); and the slot's
    SSM conv/recurrent state is RESUMED, not zeroed -- the recurrent
    families' exact-length, no-padding discipline extends to chunks (every
    chunk is exact, only the last may be bucket-padded by the engine for
    attention-only families). The caller owns table/length updates exactly
    as for :func:`paged_prefill`.

    ``with_logits=False`` skips the unembed projection and returns
    ``(None, state)`` -- only the LAST chunk's logits are ever sampled, so
    intermediate chunks need not pay the vocab GEMM (one compile bucket
    per (chunk length, with_logits) pair).

    ``kv_pages``: STATIC bound on live block-table entries, derived by the
    engine from the request's admission-time (padded) prompt footprint --
    the gather attention then contracts ``kv_pages * page`` keys instead
    of the full table capacity (one compile bucket per (chunk length,
    kv_pages) pair; ``None`` keeps the capacity-wide gather).
    """
    h = embed_inputs(cfg, params, tokens, with_meta=False)
    b, t, _ = h.shape                                  # b == 1
    positions = jnp.broadcast_to(start + jnp.arange(t)[None], (b, t))
    win_np = layer_windows(cfg, t)
    windows = jnp.asarray(win_np)
    static_win = uniform_window(win_np)
    bases = jnp.asarray(layer_rope_bases(cfg))
    zero_len = jnp.zeros((1,), jnp.int32)

    def block(h, bp, win, base, kvc, conv, st):
        ssc = None
        if conv is not None:
            ssc = ssm.SSMCache(jax.lax.dynamic_slice_in_dim(conv, slot, 1, 0),
                               jax.lax.dynamic_slice_in_dim(st, slot, 1, 0))
        h, kvc, ssc = _block_apply(engine, cfg, bp, h, positions, win, base,
                                   kv_cache=kvc, ssm_cache=ssc,
                                   window_static=static_win,
                                   prefill_start=start, kv_pages=kv_pages)
        if ssc is not None:
            conv = jax.lax.dynamic_update_slice_in_dim(
                conv, ssc.conv.astype(conv.dtype), slot, 0)
            st = jax.lax.dynamic_update_slice_in_dim(
                st, ssc.state.astype(st.dtype), slot, 0)
        return h, kvc, conv, st

    h, state = _scan_paged(
        cfg, params, h, state, windows, bases,
        lambda k, v, li: attn.PagedKVCache(k, v, pages[None], zero_len,
                                           page_size, li), block)
    logits = unembed(engine, cfg, params, h) if with_logits else None
    return logits, state


def paged_decode_step(engine: GemminiInstance, params: Params,
                      cfg: ModelConfig, tokens: jnp.ndarray,
                      state: PagedDecodeState, active: jnp.ndarray, *,
                      page_size: int
                      ) -> Tuple[jnp.ndarray, PagedDecodeState]:
    """One continuous-batching decode step: every slot advances one token.

    tokens: (slots, 1) [or (slots, 1, n_q)]; active: (slots,) bool -- slots
    that are empty or whose request finished/preempted decode padding
    (static shapes) but write to the trash page and keep frozen lengths,
    so they can never touch pages owned by live requests. Each slot ropes
    and attends at its OWN position (``lengths[slot]``) -- the per-request
    raggedness the static-batch ``decode_step`` cannot express.

    Inactive slots' conv/SSM state is frozen too (the recurrent-state
    analog of the trash page): a slot mid-way through a *chunked* prefill
    sits in the decode batch as padding, and letting the padding token
    advance its recurrent state would corrupt the state the next chunk
    resumes from.
    """
    if cfg.n_codebooks > 1:
        h = sum(layers.embed_apply(params["embed"][i], tokens[..., i])
                for i in range(cfg.n_codebooks))
    else:
        h = layers.embed_apply(params["embed"], tokens,
                               scale_by_sqrt_dim=cfg.embed_scale)
    positions = state.lengths[:, None]                 # (slots, 1)
    win_np = layer_windows(cfg, 0)
    windows = jnp.asarray(win_np)
    static_win = uniform_window(win_np)
    bases = jnp.asarray(layer_rope_bases(cfg))
    trash = state.kv_k.shape[2] - 1 if state.kv_k is not None else 0

    def block(h, bp, win, base, kvc, conv, st):
        ssc = ssm.SSMCache(conv, st) if conv is not None else None
        h, kvc, ssc = _block_apply(engine, cfg, bp, h, positions, win, base,
                                   kv_cache=kvc, ssm_cache=ssc,
                                   window_static=static_win)
        if ssc is not None:
            conv = jnp.where(active[:, None, None],
                             ssc.conv.astype(conv.dtype), conv)
            st = jnp.where(active[:, None, None, None],
                           ssc.state.astype(st.dtype), st)
        return h, kvc, conv, st

    h, state = _scan_paged(
        cfg, params, h, state, windows, bases,
        lambda k, v, li: attn.PagedKVCache(k, v, state.tables, state.lengths,
                                           page_size, li, active, trash),
        block)
    logits = unembed(engine, cfg, params, h)
    lengths = jnp.where(active, state.lengths + 1, state.lengths)
    return logits, state._replace(lengths=lengths)
