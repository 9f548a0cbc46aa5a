"""Core layers, built on the Gemmini engine substrate.

Every dense projection routes through ``GemminiInstance.matmul`` so the
paper's generated GEMM engine is the compute substrate of every assigned
architecture (the paper's own thesis: GEMM is the common kernel). In the
dry-run/XLA backend this is a plain dot that XLA partitions; on TPU it is
the Pallas engine kernel.

Pure functional style (no flax): ``init_*`` builds parameter pytrees (nested
dicts of jnp arrays); ``apply`` functions are free of Python state.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.generator import GemminiInstance
from repro.kernels import ops

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, *, dtype=jnp.bfloat16,
               scale: Optional[float] = None) -> jnp.ndarray:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, *, dtype=jnp.bfloat16) -> jnp.ndarray:
    # 1/sqrt(d): with gemma-style sqrt(d) embed scaling the residual stream
    # starts O(1), and tied-unembed logits start O(1) (loss ~ ln(vocab)).
    return (jax.random.normal(key, (vocab, d), jnp.float32) /
            math.sqrt(d)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros((d,), dtype)  # gemma-style (1 + scale) parameterization


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-6,
            zero_centered: bool = True) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale.astype(jnp.float32)) if zero_centered \
        else scale.astype(jnp.float32)
    return (y * w).astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray, *, base: float = 10000.0,
         scaling: float = 1.0) -> jnp.ndarray:
    """x: (..., T, H, D); positions: broadcastable to (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq / scaling  # (...,T,half)
    ang = ang[..., None, :]                                          # (...,T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# engine-backed projections
# ---------------------------------------------------------------------------
class StackedWeight(NamedTuple):
    """Layer ``layer`` of a stack of projection weights ``(L, d_in,
    d_out)``, left in the stack: the paged layer scan hands each block
    these in place of per-layer slices, and the engine GEMM reads the
    layer from the stack by index, so no layer's matrix is copied out."""

    stack: jnp.ndarray
    layer: jnp.ndarray                 # int32 scalar, may be traced


def project(engine, x: jnp.ndarray, w: Union[jnp.ndarray, StackedWeight],
            b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """y = x @ w (+ b) on the Gemmini engine; x: (..., d_in), w: (d_in, d_out)
    or a :class:`StackedWeight` of that shape.

    ``engine`` is the dispatch value: an elaborated
    :class:`GemminiInstance` or a bare
    :class:`repro.core.context.ExecutionContext` -- both expose
    ``backend`` and ``matmul``, and a mesh'd context runs the engine
    kernel in shard_map at per-device M."""
    layer = None
    if isinstance(w, StackedWeight):
        w, layer = w.stack, w.layer
    if engine.backend == "xla":
        w = ops.weight_at(w, layer)
        # Float LM path: keep XLA free to fuse/partition; numerics equal to
        # the engine's float datapath (fp32 accumulate). "xla_twin"
        # deliberately does NOT take this shortcut: the degraded-mode twin
        # must round through the same engine datapath as the kernel
        # backends (ctx.matmul lowers it to plain XLA ops anyway).
        y = jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = y.astype(x.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)
        return y
    # Engine path: the bias rides the engine's native D input (accumulated
    # at acc dtype inside the fused epilogue, Gemmini's D matrix) instead
    # of a separate post-engine add -- and the tile plan resolves with
    # has_bias=True, the same fingerprint warm_model_plans pre-populates.
    return engine.matmul(x, w, d=b, layer=layer)


def mlp_init(key, d: int, d_ff: int, *, dtype=jnp.bfloat16,
             gated: bool = True) -> Params:
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], d, d_ff, dtype=dtype),
         "wo": dense_init(ks[1], d_ff, d, dtype=dtype)}
    if gated:
        p["wg"] = dense_init(ks[2], d, d_ff, dtype=dtype)
    return p


def mlp_apply(engine: GemminiInstance, p: Params, x: jnp.ndarray, *,
              activation: str = "silu") -> jnp.ndarray:
    act = {"silu": jax.nn.silu, "gelu": lambda v: jax.nn.gelu(v, approximate=True),
           "relu": jax.nn.relu}[activation]
    h = project(engine, x, p["wi"])
    if "wg" in p:
        h = act(project(engine, x, p["wg"])) * h
    else:
        h = act(h)
    return project(engine, h, p["wo"])


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------
def embed_apply(table: jnp.ndarray, tokens: jnp.ndarray, *,
                scale_by_sqrt_dim: bool = False) -> jnp.ndarray:
    y = jnp.take(table, tokens, axis=0)
    if scale_by_sqrt_dim:
        y = (y.astype(jnp.float32) * math.sqrt(table.shape[1])).astype(y.dtype)
    return y


def unembed_apply(engine: GemminiInstance, table: jnp.ndarray,
                  x: jnp.ndarray, *, softcap: Optional[float] = None
                  ) -> jnp.ndarray:
    logits = project(engine, x, table.T)
    logits = logits.astype(jnp.float32)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


# ---------------------------------------------------------------------------
# causal depthwise conv1d (mamba2 prefix conv; also the depthwise op the
# paper assigns to the host -- see paper/bench_system_amdahl.py)
# ---------------------------------------------------------------------------
def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                  state: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, C), w: (K, C) depthwise. Returns (y, new_state).

    state: (B, K-1, C) trailing inputs from the previous segment (decode).
    """
    k = w.shape[0]
    b, t, c = x.shape
    if state is None:
        state = jnp.zeros((b, k - 1, c), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)            # (B, T+K-1, C)
    y = jnp.zeros((b, t, c), jnp.float32)
    for i in range(k):                                  # K is tiny (4)
        y = y + xp[:, i:i + t, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    new_state = xp[:, -(k - 1):, :]
    return y.astype(x.dtype), new_state
