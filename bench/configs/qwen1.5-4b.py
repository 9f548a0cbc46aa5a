"""qwen1.5-4b: the weights the benchmark makes, the plain float32 reference,
and the operations and bytes of each step, for the sizes in
``qwen1.5-4b.json``.

The architecture is Qwen2's, as the model's published ``config.json``
describes it: pre-norm decoder blocks with RMSNorm, multi-head attention
with a bias on the q, k and v projections, rotary position embeddings over
the two halves of each head, a SiLU-gated MLP, a final RMSNorm and an
untied output head.

Weights are stored in the layout the serving engine takes them (stacked
over layers, each projection as (d_in, d_out)), and a norm's weight is
stored as its offset from 1. The reference reads them in that layout and
imports nothing of the program.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench import refcommon as rc

BF16 = jnp.bfloat16


def dims(cfg: Dict) -> Dict:
    c = cfg["config"]
    h = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"], "H": h,
            "KV": c["num_key_value_heads"], "hd": c["hidden_size"] // h,
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def program_fields(cfg: Dict) -> Dict:
    """The fields of the program's registered model that must equal this
    file's sizes."""
    m = dims(cfg)
    return {"n_layers": m["L"], "d_model": m["d"], "n_heads": m["H"],
            "n_kv_heads": m["KV"], "head_dim": m["hd"], "d_ff": m["ff"],
            "vocab": m["V"], "rope_base": m["theta"], "qkv_bias": True,
            "tie_embeddings": cfg["config"]["tie_word_embeddings"],
            "activation": cfg["config"]["hidden_act"]}


# ---------------------------------------------------------------------------
# weights, from the seed, on the device, in bfloat16
# ---------------------------------------------------------------------------
def _normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _per_layer(key, n, shape, std, dtype=BF16):
    """(n, *shape), drawn one layer at a time so that no float32 copy of
    the whole stack is ever held."""
    return jax.lax.map(lambda k: _normal(k, shape, std, dtype),
                       jax.random.split(key, n))


def init_params(key, cfg: Dict) -> Dict:
    m = dims(cfg)
    L, d, H, KV, hd, ff, V = (m[k] for k in ("L", "d", "H", "KV", "hd",
                                             "ff", "V"))
    k = iter(jax.random.split(key, 16))
    f32 = jnp.float32
    attn = {"wq": _per_layer(next(k), L, (d, H * hd), d ** -0.5),
            "wk": _per_layer(next(k), L, (d, KV * hd), d ** -0.5),
            "wv": _per_layer(next(k), L, (d, KV * hd), d ** -0.5),
            "wo": _per_layer(next(k), L, (H * hd, d), (H * hd) ** -0.5),
            "bq": _normal(next(k), (L, H * hd), 0.1),
            "bk": _normal(next(k), (L, KV * hd), 0.1),
            "bv": _normal(next(k), (L, KV * hd), 0.1)}
    mlp = {"wi": _per_layer(next(k), L, (d, ff), d ** -0.5),
           "wg": _per_layer(next(k), L, (d, ff), d ** -0.5),
           "wo": _per_layer(next(k), L, (ff, d), ff ** -0.5)}
    blocks = {"ln1": _normal(next(k), (L, d), 0.1, f32), "attn": attn,
              "ln2": _normal(next(k), (L, d), 0.1, f32), "mlp": mlp}
    return {"embed": _per_layer(next(k), 8, (V // 8, d), d ** -0.5
                                ).reshape(V, d),
            "blocks": blocks,
            "final_norm": _normal(next(k), (d,), 0.1, f32),
            "unembed": _per_layer(next(k), 8, (d, V // 8), d ** -0.5
                                  ).transpose(1, 0, 2).reshape(d, V)}


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------
def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(params, tokens, cfg: Dict, quant: bool) -> jnp.ndarray:
    """Final-normed hidden states (S * T, d) of the whole forward pass over
    ``tokens`` (S, T), each row attending causally to its own sequence."""
    m = dims(cfg)
    s, t = tokens.shape
    H, KV, hd, eps = m["H"], m["KV"], m["hd"], m["eps"]
    half = hd // 2
    inv = m["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    mm = lambda x, w: rc.matmul(x, w, quant)

    def layer(x, bp):
        a = bp["attn"]
        h = rc.rmsnorm(x, 1.0 + bp["ln1"], eps)
        q = (mm(h, a["wq"]) + a["bq"].astype(jnp.float32)).reshape(s, t, H, hd)
        k = (mm(h, a["wk"]) + a["bk"].astype(jnp.float32)).reshape(s, t, KV, hd)
        v = (mm(h, a["wv"]) + a["bv"].astype(jnp.float32)).reshape(s, t, KV, hd)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        sc = jnp.einsum("sqhd,skhd->shqk", q, k,
                        precision=rc.HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("shqk,skhd->sqhd", p, v, precision=rc.HIGHEST)
        x = x + mm(o.reshape(s, t, H * hd), a["wo"])
        h = rc.rmsnorm(x, 1.0 + bp["ln2"], eps)
        f = bp["mlp"]
        x = x + mm(jax.nn.silu(mm(h, f["wg"])) * mm(h, f["wi"]), f["wo"])
        return x, None

    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rc.rmsnorm(x, 1.0 + params["final_norm"], eps).reshape(s * t, -1)


def head(params, cfg: Dict):
    return lambda h, quant: rc.matmul(h, params["unembed"], quant)


# ---------------------------------------------------------------------------
# operations and bytes of one dispatched step
# ---------------------------------------------------------------------------
def _projections(m: Dict, logits: bool):
    """(K, N, has_bias) of every engine GEMM in one step."""
    d, H, KV, hd, ff = m["d"], m["H"], m["KV"], m["hd"], m["ff"]
    per_layer = [(d, H * hd, True), (d, KV * hd, True), (d, KV * hd, True),
                 (H * hd, d, False), (d, ff, False), (d, ff, False),
                 (ff, d, False)]
    out = per_layer * m["L"]
    if logits:
        out = out + [(d, m["V"], False)]
    return out


def gemm_cost(m: Dict, rows: int, logits: bool):
    """Operations and the least HBM bytes (bf16 operands, each read or
    written once) of the step's projection GEMMs at ``rows`` rows."""
    return [[2.0 * rows * k * n,
             2.0 * (k * n + rows * k + rows * n + (n if bias else 0))]
            for k, n, bias in _projections(m, logits)]


def step_costs(cfg: Dict, e: Dict) -> Dict:
    """What one dispatched step ``e`` requires.

    ``e``: ``which``; ``rows``, the rows the projection GEMMs run on;
    ``tokens``, the true tokens among them; ``start``, a chunk's first
    cache position; ``keys``, each live decode slot's attended keys;
    ``page``, the page size. Returns per kernel a list of [operations,
    bytes], one per call or group of identical calls, and
    ``model``: the operations the true tokens require (the projections,
    attention over the context, and the output head for the rows that are
    sampled)."""
    m = dims(cfg)
    L, H, hd, KV = m["L"], m["H"], m["hd"], m["KV"]
    dec = e["which"] == "decode"
    logits = e["which"] in ("decode", "prefill", "chunk")
    out = {"gemm": gemm_cost(m, e["rows"], logits)}
    proj = sum(2.0 * k * n for k, n, _ in _projections(m, False))
    if dec:
        keys = e["keys"]
        page = e["page"]
        out["paged_attn"] = [[
            sum(4.0 * L * H * hd * n for n in keys),
            sum(2.0 * 2 * L * KV * hd * page * -(-n // page) for n in keys)]]
        attn = sum(4.0 * L * H * hd * n for n in keys)
        sampled = len(keys)
        toks = len(keys)
    else:
        s, n = e["start"], e["tokens"]
        # position p attends p + 1 keys
        attn = 4.0 * L * H * hd * (n * s + n * (n + 1) / 2)
        sampled = 1 if logits else 0
        toks = n
    out["model"] = toks * proj + attn + sampled * 2.0 * m["d"] * m["V"]
    return out
