"""Jittable train / serve steps with full sharding annotations.

These are the functions the launcher jits and the dry-run lowers: one
train_step (fwd + bwd + AdamW/ZeRO-1 update) and one serve_step (single-token
decode against a sharded KV/SSM cache). Grad accumulation and the elastic /
fault-tolerance wrappers live in launch/train.py and runtime/.

``engine`` throughout is the dispatch value (an elaborated
:class:`GemminiInstance` or a bare
:class:`repro.core.context.ExecutionContext`); give it a mesh
(``with_mesh``) and the pallas/interpret kernels inside these jitted steps
run under shard_map with per-device shapes, which is what makes tuned
Pallas kernels legal in a GSPMD-partitioned step.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.generator import GemminiInstance
from repro.launch import sharding as shd
from repro.models import transformer as tf
from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any
    opt: Dict[str, Any]
    step: jnp.ndarray


def make_train_step(engine: GemminiInstance, cfg: tf.ModelConfig,
                    opt_cfg: adamw.AdamWConfig, mesh, batch: int, seq: int,
                    *, grad_accum: int = 1):
    """Returns train_step(state, batch_dict) -> (state, metrics)."""
    res_shd = shd.to_named(shd.residual_spec(cfg, mesh, batch, seq), mesh)
    log_shd = shd.to_named(shd.logits_spec(cfg, mesh, batch), mesh)

    def loss(params, tokens, labels, extra):
        return tf.loss_fn(engine, params, cfg, tokens, labels, extra,
                          remat=True, residual_sharding=res_shd,
                          logits_sharding=log_shd)

    def train_step(state: TrainState, batch_dict) -> Tuple[TrainState, Dict]:
        tokens = batch_dict["tokens"]
        labels = batch_dict["labels"]
        extra = batch_dict.get("extra_embeds")
        if grad_accum == 1:
            lval, grads = jax.value_and_grad(loss)(state.params, tokens,
                                                   labels, extra)
        else:
            mb_tok = tokens.reshape(grad_accum, -1, *tokens.shape[1:])
            mb_lab = labels.reshape(grad_accum, -1, *labels.shape[1:])
            mb_ext = (None if extra is None else
                      extra.reshape(grad_accum, -1, *extra.shape[1:]))

            def acc_fn(carry, mb):
                tot, g = carry
                t, l, e = mb
                lv, gi = jax.value_and_grad(loss)(state.params, t, l, e)
                return (tot + lv, jax.tree.map(jnp.add, g, gi)), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            xs = (mb_tok, mb_lab, mb_ext) if mb_ext is not None \
                else (mb_tok, mb_lab, mb_tok)  # dummy third
            if mb_ext is None:
                def acc_fn2(carry, mb):
                    tot, g = carry
                    t, l, _ = mb
                    lv, gi = jax.value_and_grad(loss)(state.params, t, l,
                                                      None)
                    return (tot + lv, jax.tree.map(jnp.add, g, gi)), None
                (lsum, grads), _ = jax.lax.scan(acc_fn2, (0.0, zeros), xs)
            else:
                (lsum, grads), _ = jax.lax.scan(acc_fn, (0.0, zeros), xs)
            lval = lsum / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        new_params, new_opt, om = adamw.adamw_update(
            opt_cfg, state.params, grads, state.opt)
        metrics = {"loss": lval, **om}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def make_prefill_step(engine: GemminiInstance, cfg: tf.ModelConfig, mesh,
                      batch: int, seq: int):
    """Inference prefill: forward over the prompt, return last-token logits.

    (Roofline-wise prefill == forward; the cache write is a minor term and is
    exercised by the serving gate, tests/test_serving_families.py.)
    """
    res_shd = shd.to_named(shd.residual_spec(cfg, mesh, batch, seq), mesh)
    log_shd = shd.to_named(shd.logits_spec(cfg, mesh, batch), mesh)

    def prefill_step(params, batch_dict):
        logits = tf.forward(engine, params, cfg, batch_dict["tokens"],
                            batch_dict.get("extra_embeds"),
                            residual_sharding=res_shd,
                            logits_sharding=log_shd)
        return logits[:, -1]

    return prefill_step


def make_serve_step(engine: GemminiInstance, cfg: tf.ModelConfig, mesh,
                    batch: int, max_seq: int):
    """One-token decode against a dense KV/SSM cache of ``max_seq``: the
    oracle's ``decode_step``, not the serving engine's paged step."""

    def serve_step(params, tokens, state: tf.DecodeState):
        logits, new_state = tf.decode_step(engine, params, cfg, tokens,
                                           state)
        return logits, new_state

    return serve_step


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no device allocation)
# ---------------------------------------------------------------------------
SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

N_VLM_TOKENS = 576   # anyres base-tile patch embeddings (stub frontend)


def param_shapes(cfg: tf.ModelConfig):
    """ShapeDtypeStruct pytree of the model params (no allocation)."""
    return jax.eval_shape(
        functools.partial(tf.init_params, cfg=cfg), jax.random.PRNGKey(0))


def opt_shapes(params_shape):
    return jax.eval_shape(adamw.adamw_init, params_shape)


def _with_shardings(tree_shapes, tree_specs, mesh):
    def attach(s, spec):
        return jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=jax.sharding.NamedSharding(mesh, spec))
    return jax.tree.map(attach, tree_shapes, tree_specs)


def input_specs(cfg: tf.ModelConfig, shape_name: str, mesh) -> Dict[str, Any]:
    """All inputs for the step that this (arch x shape) cell lowers.

    Returns dict with 'kind', 'args' (tuple of ShapeDtypeStructs in step
    order) and 'out_shardings'.
    """
    info = SHAPES[shape_name]
    batch, seq = info["batch"], info["seq"]
    kind = info["kind"]
    tok_nd = 3 if cfg.n_codebooks > 1 else 2
    tspec = shd.tokens_spec(mesh, batch, tok_nd)
    tok_shape = (batch, seq, cfg.n_codebooks) if tok_nd == 3 \
        else (batch, seq)

    pshapes = param_shapes(cfg)
    pspecs = shd.param_specs(pshapes, mesh)
    params = _with_shardings(pshapes, pspecs, mesh)

    def tok_struct(shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=jax.sharding.NamedSharding(mesh, tspec))

    if kind in ("train", "prefill"):
        text_seq = seq
        extra = None
        if cfg.modality == "vlm":
            text_seq = seq - N_VLM_TOKENS
            extra = jax.ShapeDtypeStruct(
                (batch, N_VLM_TOKENS, cfg.d_model), cfg.dtype,
                sharding=jax.sharding.NamedSharding(
                    mesh, shd.tokens_spec(mesh, batch, 3)))
        tshape = (batch, text_seq, cfg.n_codebooks) if tok_nd == 3 \
            else (batch, text_seq)
        batch_dict = {"tokens": tok_struct(tshape)}
        if kind == "train":
            batch_dict["labels"] = tok_struct(tshape)
        if extra is not None:
            batch_dict["extra_embeds"] = extra
        if kind == "train":
            oshapes = opt_shapes(pshapes)
            ospecs = shd.opt_state_specs(pshapes, mesh)
            opt = _with_shardings(oshapes, ospecs, mesh)
            step0 = jax.ShapeDtypeStruct(
                (), jnp.int32,
                sharding=jax.sharding.NamedSharding(mesh, shd.P()))
            from repro.launch.steps import TrainState
            state = TrainState(params=params, opt=opt, step=step0)
            return dict(kind=kind, args=(state, batch_dict), batch=batch,
                        seq=seq)
        return dict(kind=kind, args=(params, batch_dict), batch=batch,
                    seq=seq)

    # decode: one new token with a cache of `seq`
    dshape = (batch, 1, cfg.n_codebooks) if tok_nd == 3 else (batch, 1)
    tokens = tok_struct(dshape)
    sshapes = jax.eval_shape(
        functools.partial(tf.init_decode_state, cfg, batch, seq))
    sspecs = shd.decode_state_specs(cfg, mesh, batch, seq)
    state = _with_shardings(sshapes, sspecs, mesh)
    return dict(kind=kind, args=(params, tokens, state), batch=batch,
                seq=seq)
