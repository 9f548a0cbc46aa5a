"""End-to-end training driver (the launcher).

Composes every substrate layer: config registry -> Gemmini engine ->
sharded train step -> synthetic data pipeline -> checkpoint manager ->
straggler detection -> restart/elastic loop. Runs real steps on whatever
devices exist (CPU smoke configs through 512-chip production meshes -- the
mesh is chosen from the live device count).

Usage (CPU, reduced config, full fault-tolerant loop):

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 20

Production XLA flags for compute/comm overlap (latency-hiding scheduler)
are applied when --xla-lhs is passed; they must be set before jax import,
so the flag re-execs the process with the env prepared.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import sys
import time
from typing import Any

LHS_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_overlap_compute_collective_tc=true"
)


def _maybe_reexec_with_lhs():
    if "--xla-lhs" in sys.argv and not os.environ.get("_REPRO_LHS"):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + LHS_FLAGS).strip()
        env["_REPRO_LHS"] = "1"
        os.execve(sys.executable, [sys.executable] + sys.argv, env)


_maybe_reexec_with_lhs()

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro import configs                                       # noqa: E402
from repro.checkpoint import CheckpointManager                  # noqa: E402
from repro.core import flags                                    # noqa: E402
from repro.core.config import GemminiConfig                     # noqa: E402
from repro.core.generator import (default_engine_backend,      # noqa: E402
                                  elaborate)
from repro.data import SyntheticLM, SyntheticLMConfig, \
    make_global_batch                                           # noqa: E402
from repro.launch import platform                               # noqa: E402
from repro.launch import sharding as shd                        # noqa: E402
from repro.launch.mesh import activate_mesh, make_mesh          # noqa: E402
from repro.launch import steps as steps_lib                     # noqa: E402
from repro.models import transformer as tf                      # noqa: E402
from repro.optim import adamw                                   # noqa: E402
from repro.runtime import (RestartPolicy, StragglerDetector,    # noqa: E402
                           run_with_restarts)


def pick_mesh(tp_hint: int = 0):
    """Largest (data, model) mesh the live devices support."""
    n = jax.device_count()
    tp = tp_hint or max(1, min(16, n))
    while n % tp:
        tp //= 2
    return make_mesh((n // tp, tp), ("data", "model"))


@dataclasses.dataclass
class RunResult:
    steps_done: int
    final_loss: float
    losses: list
    straggler_steps: int
    state: Any = None               # the final TrainState, on the mesh


def train_once(args, model_cfg, pods: int) -> RunResult:
    mesh = pick_mesh(args.tp)
    # Mesh-aware dispatch (ExecutionContext under the hood): on a pallas/
    # interpret engine every op runs in shard_map and resolves its tuned
    # schedule at the PER-DEVICE shapes -- the same shapes the shard-aware
    # warm below populates. The xla backend (CPU CI) ignores the mesh and
    # stays on the GSPMD-partitioned plan-free reference.
    engine = elaborate(GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                                     output_dtype="bf16"),
                       args.backend or default_engine_backend()
                       ).with_mesh(mesh, axis=shd.data_axis(mesh))
    opt_cfg = adamw.AdamWConfig(lr=args.lr)
    batch, seq = args.batch, args.seq

    if flags.get("tune_mode") != "off":
        # Warm the schedule cache for every GEMM/attention shape a train
        # step runs, shard-aware: the partitioner splits the global batch
        # over the mesh's data axis, so each device launches the per-device
        # M -- warming the global M would populate entries no kernel hits.
        from repro import tune
        data_shards = engine.ctx.n_shards
        stats = tune.warm_model_plans(engine.cfg, model_cfg, batch, seq,
                                      include_decode=False,
                                      n_shards=data_shards)
        print(f"[train] plan warmup ({flags.get('tune_mode')}, "
              f"{data_shards} data shard(s)): "
              f"{stats['gemm_shapes']} gemm + {stats['attn_shapes']} attn "
              f"shapes, {stats['cache_hits']} cache hits, "
              f"{stats['cache_misses']} misses")

    data_cfg = SyntheticLMConfig(
        vocab=model_cfg.vocab, seq=seq, global_batch=batch, seed=args.seed,
        n_codebooks=model_cfg.n_codebooks)
    gen = SyntheticLM(data_cfg)
    tok_nd = 3 if model_cfg.n_codebooks > 1 else 2
    tok_sharding = jax.sharding.NamedSharding(
        mesh, shd.tokens_spec(mesh, batch, tok_nd))

    with activate_mesh(mesh):
        pshapes = steps_lib.param_shapes(model_cfg)
        pspecs = shd.param_specs(pshapes, mesh)
        pshard = shd.to_named(pspecs, mesh)
        oshapes = steps_lib.opt_shapes(pshapes)
        ospecs = shd.opt_state_specs(pshapes, mesh)
        oshard = shd.to_named(ospecs, mesh)

        mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir \
            else None
        start_step = 0
        state = None
        if mgr is not None:
            target = steps_lib.TrainState(
                params=pshapes, opt=oshapes,
                step=jax.ShapeDtypeStruct((), jnp.int32))
            tshard = steps_lib.TrainState(
                params=pshard, opt=oshard,
                step=jax.sharding.NamedSharding(mesh, shd.P()))
            step_found, restored = mgr.restore_latest(
                target, tshard, expect_meta={"arch": model_cfg.name})
            if step_found is not None:
                start_step, state = step_found, restored
                print(f"[train] restored checkpoint step={start_step} "
                      f"(mesh={tuple(mesh.shape.items())})")
        if state is None:
            init = jax.jit(
                functools.partial(tf.init_params, cfg=model_cfg),
                out_shardings=pshard)
            params = init(jax.random.PRNGKey(args.seed))
            opt = jax.jit(adamw.adamw_init, out_shardings=oshard)(params)
            state = steps_lib.TrainState(
                params=params, opt=opt, step=jnp.zeros((), jnp.int32))

        train_step = jax.jit(
            steps_lib.make_train_step(engine, model_cfg, opt_cfg, mesh,
                                      batch=batch, seq=seq,
                                      grad_accum=args.grad_accum),
            donate_argnums=(0,))

        detector = StragglerDetector()
        losses, stragglers = [], 0
        step = start_step
        try:
            while step < args.steps:
                if args.fail_at is not None and step == args.fail_at \
                        and not os.environ.get("_REPRO_FAILED"):
                    os.environ["_REPRO_FAILED"] = "1"
                    raise RuntimeError(f"injected failure at step {step}")
                t0 = time.time()
                batch_dict = make_global_batch(gen, step, tok_sharding)
                if model_cfg.modality == "vlm":
                    batch_dict = make_global_batch(
                        gen, step, tok_sharding,
                        extra_embed_dim=model_cfg.d_model,
                        extra_tokens=steps_lib.N_VLM_TOKENS)
                state, metrics = train_step(state, batch_dict)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                if detector.observe(dt):
                    stragglers += 1
                    print(f"[train] step {step}: straggler ({dt*1e3:.0f}ms)")
                losses.append(loss)
                if step % args.log_every == 0:
                    print(f"[train] step {step:5d} loss={loss:.4f} "
                          f"({dt*1e3:.0f}ms)")
                step += 1
                if mgr is not None and step % args.ckpt_every == 0:
                    mgr.save_async(step, state,
                                   extra_meta={"arch": model_cfg.name})
            if mgr is not None:
                mgr.save(step, state, extra_meta={"arch": model_cfg.name})
            return RunResult(step, losses[-1] if losses else float("nan"),
                             losses, stragglers, state)
        finally:
            # Flush any in-flight async checkpoint before this attempt
            # unwinds: an in-process restart (run_with_restarts) builds a
            # fresh manager and calls restore_latest immediately -- racing
            # the daemon writer would make it restart from step 0.
            if mgr is not None:
                mgr.wait()


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject one failure at this step (FT demo)")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--xla-lhs", action="store_true",
                    help="enable latency-hiding-scheduler XLA flags")
    ap.add_argument("--tune", choices=flags.TUNE_MODES, default=None,
                    help="tile-plan autotuning mode (default: $GEMMINI_TUNE)")
    ap.add_argument("--backend", choices=("xla", "pallas", "interpret"),
                    default="",
                    help="engine ExecutionContext backend (default: pallas "
                         "on TPU hosts, xla elsewhere)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    platform.use_compile_cache()
    print(f"[train] "
          f"{platform.device_banner(args.backend or default_engine_backend())}")
    # Always re-set: set_flag validates, so a typo'd $GEMMINI_TUNE fails at
    # startup instead of (maybe never) at the first plan resolution.
    flags.set_flag("tune_mode", args.tune if args.tune is not None
                   else flags.get("tune_mode"))

    model_cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)

    def make_runner(attempt, pods):
        if attempt:
            print(f"[train] restart #{attempt} on {pods} pod(s)")
        return lambda: train_once(args, model_cfg, pods)

    result, attempts, pods = run_with_restarts(
        make_runner, RestartPolicy(max_failures=args.max_restarts),
        n_pods=1,
        on_failure=lambda a, e: print(f"[train] FAILURE (attempt {a}): {e}"))
    print(f"[train] done: {result.steps_done} steps, "
          f"final_loss={result.final_loss:.4f}, attempts={attempts}, "
          f"stragglers={result.straggler_steps}")
    return result


if __name__ == "__main__":
    main()
