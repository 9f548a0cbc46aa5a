"""Pipeline parallelism: GPipe-style stage loop over a ``stage`` mesh axis.

Not enabled by default at 512 chips (DP x TP fills the mesh; see DESIGN.md
section 4) but provided -- and tested -- as the scaling path beyond ~4k
chips, where TP hits the ICI diameter and layer stages must be split.

Mechanics (TPU-native): the layer stack is split into S stages whose
parameters are sharded over the ``stage`` mesh axis (each device group holds
only its stage's layers -- the PP memory win). Microbatches march through
the classic GPipe schedule: at tick ``t`` stage ``s`` processes microbatch
``t - s``; activations hop stage->stage+1 through ``jax.lax.ppermute``
(point-to-point neighbor traffic on the ICI torus -- never a broadcast).
The loop is a ``lax.scan``, so ``jax.grad`` differentiates straight through
the schedule: the transpose of ppermute is the reverse rotation, giving the
backward pipeline for free, with the bubble fraction (S-1)/(T+S-1) exactly
as in GPipe.

``pipeline_apply`` operates on the residual stream; embedding/unembedding
stay outside (replicated or TP-sharded as usual), which composes PP with
the DP/TP rules in launch/sharding.py: mesh axes (pod, stage, data, model).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """Reshape (L, ...) stacked layer params to (S, L/S, ...)."""
    def one(p):
        l = p.shape[0]
        if l % n_stages:
            raise ValueError(f"{l} layers not divisible into {n_stages} stages")
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])
    return jax.tree.map(one, stacked_params)


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x_microbatches: jnp.ndarray, *,
                   mesh, axis: str = "stage") -> jnp.ndarray:
    """Run microbatches through the S-stage pipeline.

    stage_fn(params_for_one_stage, h) -> h   (applies that stage's layers)
    stage_params: pytree with leading dim S (sharded over ``axis``)
    x_microbatches: (n_micro, mb, ...) residual-stream inputs
    Returns (n_micro, mb, ...) outputs (last stage's results, replicated).
    """
    n_stages = mesh.shape[axis]
    nm = x_microbatches.shape[0]

    def inner(params_local, x_local):
        # params_local leaves: (1, L/S, ...) -- this stage's slice
        params1 = jax.tree.map(lambda p: p[0], params_local)
        s = jax.lax.axis_index(axis)
        total = nm + n_stages - 1
        buf0 = jnp.zeros_like(x_local[0])
        outs0 = jnp.zeros_like(x_local)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = jnp.clip(t, 0, nm - 1)
            inp = x_local[mb_idx]
            h_in = jnp.where(s == 0, inp, buf)
            h_out = stage_fn(params1, h_in)
            out_idx = t - (n_stages - 1)
            valid = (s == n_stages - 1) & (out_idx >= 0)
            upd = jnp.where(valid, h_out,
                            outs[jnp.clip(out_idx, 0, nm - 1)])
            outs = outs.at[jnp.clip(out_idx, 0, nm - 1)].set(upd)
            buf = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(total))
        # replicate the last stage's outputs to every stage
        outs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    # other mesh axes: params/x replicated from PP's point of view (their
    # sharding is handled by the surrounding pjit partitioner)
    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(pspec, P()), out_specs=P(),
                         check_vma=False)(stage_params, x_microbatches)


def pipeline_loss_fn(stage_fn, embed_fn, unembed_loss_fn):
    """Compose embed -> pipeline -> unembed+loss for training."""

    def loss(params, tokens, labels, *, mesh, n_micro: int,
             axis: str = "stage"):
        h = embed_fn(params, tokens)                     # (B, T, D)
        b = h.shape[0]
        hm = h.reshape(n_micro, b // n_micro, *h.shape[1:])
        ym = pipeline_apply(
            lambda sp, hh: stage_fn(params, sp, hh),
            params["stages"], hm, mesh=mesh, axis=axis)
        y = ym.reshape(b, *ym.shape[2:])
        lm = labels
        return unembed_loss_fn(params, y, lm)

    return loss
