"""Pieces the plain references share: float32 products at full precision,
the fp8 rounding of the control, and the logit-gap comparison.

Nothing here imports the program under test.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def round_e4m3(x: jnp.ndarray) -> jnp.ndarray:
    """Round float32 values already scaled into [-448, 448] to the nearest
    float8 e4m3 value: 4 significant bits for normals, steps of 2^-9 below
    the smallest normal 2^-6. Written out in float32 so that it runs the
    same on every backend."""
    m, e = jnp.frexp(x)
    normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    sub = jnp.round(x * 512.0) / 512.0
    return jnp.where(jnp.abs(x) < 2.0 ** -6, sub, normal)


def fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """``x`` through float8 e4m3 with one scale per slice along ``axis``
    (its absolute maximum to 448), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return round_e4m3(x / s) * s


def matmul(x: jnp.ndarray, w: jnp.ndarray, quant: bool) -> jnp.ndarray:
    """x (..., K) @ w (K, N) in float32 at full precision; with ``quant``
    both operands first go through fp8 (per row of x, per column of w)."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant:
        x = fp8(x, -1)
        w = fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight.astype(F32)


def logit_gaps(hidden: jnp.ndarray, rows: jnp.ndarray, targets: jnp.ndarray,
               head: Callable[[jnp.ndarray, bool], jnp.ndarray], *,
               control_hidden=None, block: int = 256) -> jnp.ndarray:
    """For each selected row of ``hidden`` (S*T, D): how far the logit of
    the row's token lies below the row's best logit, both read from the
    float32 head. The token is ``targets`` (the served token), or, given
    the control's hidden states, the token that the control's fp8 head
    puts first at that row."""
    r = rows.shape[0]
    d = hidden.shape[-1]
    hb = hidden[rows].reshape(r // block, block, d)
    tb = targets.reshape(r // block, block)
    cb = hb if control_hidden is None else \
        control_hidden[rows].reshape(r // block, block, d)

    def one(args):
        h, t, hc = args
        logits = head(h, False)
        if control_hidden is not None:
            t = jnp.argmax(head(hc, True), axis=-1)
        got = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.lax.map(one, (hb, tb, cb)).reshape(r)
