#!/usr/bin/env python3
"""Bring-up check on the chip: the system's main path at qwen1.5-4b's
published widths and depth (40 layers, d=2560, 20x128 heads, d_ff=6912,
vocab 151,936), with random weights from a seed.

  python chip_smoke.py             # one chip: the serving engine
  python chip_smoke.py --chips 4   # four chips: the trainer's mesh

One chip serves 4 requests of 512 prompt tokens and 32 new tokens each
through ``repro.launch.serve.serve`` on the ``pallas`` backend (submit, the
scheduler, the paged cache, chunked prefill and decode), then checks:

  * every request finished with 32 tokens inside the vocabulary;
  * every jitted step that ran holds Mosaic kernels (``tpu_custom_call``
    in its compiled HLO), so a quiet demotion to XLA cannot pass;
  * a fresh prompt's first prefill step, its next chunk and one decode
    step give the same logits on the ``pallas`` and the ``xla`` contexts,
    within the tolerance stated at ``LOGIT_RTOL``.

Four chips run a few train steps on the trainer's (data, model) mesh over
every device, on the ``pallas`` backend and then on ``xla``, and compare
the losses within ``LOSS_ATOL``; each device's memory in use is printed.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or when any phase fails. Its last line on success is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Everything runs in this one process: a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-4b"
SEED = 0
PROMPT, GEN, SLOTS = 512, 32, 4
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 256

# Relative L2 distance allowed between the pallas and xla logits. Both
# contexts run the same bf16 weights with f32 accumulation, but round at
# different points: the kernel adds the QKV bias in its f32 accumulator
# and XLA after a bf16 round, the two sum K in different orders, and the
# two attention implementations block keys differently. Each of a layer's
# 7 projections and its attention then lands on the other bf16 neighbour
# (2^-9 relative) for part of its outputs, independently of the other
# layers, so the distance grows as a random walk, with the square root of
# depth or slower. Measured between the interpreted kernels and xla at
# qwen1.5-4b widths on the CPU: 4.6e-3 at 1 layer, 9.5e-3 at 4, 1.5e-2 at
# 16, which gives 2.3e-2 at 40 layers; a TPU v5e gave 2.0e-2. A kernel
# that reads a wrong page, block or row changes the logits at their own
# scale, a distance near 1.
LOGIT_RTOL = 5e-2

# Absolute difference allowed between the pallas and xla losses of each
# train step. The loss starts near ln(151936) = 11.9 and each step's
# loss is one mean over 1,024 tokens; the two backends round the same
# bf16 projections and their gradients at different points, which moves
# the loss by far less than 1e-2.
LOSS_ATOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def require_tpu(n_chips: int):
    """The devices, when JAX found ``n_chips`` TPUs or more; otherwise exit
    non-zero. JAX carries on with the CPU when libtpu fails to start, so
    this check is what keeps a CPU run from passing for a chip run."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"[chip_smoke] no TPU: JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < n_chips:
        raise SystemExit(f"[chip_smoke] {n_chips} chips asked for, JAX "
                         f"found {len(devs)}")
    return devs


def device_memory() -> str:
    import jax
    return ", ".join(
        f"{d.id}: {d.memory_stats()['bytes_in_use']} in use / "
        f"{d.memory_stats()['peak_bytes_in_use']} peak"
        for d in jax.devices())


# ---------------------------------------------------------------------------
# one chip: the serving engine
# ---------------------------------------------------------------------------
def serve_phase(cfg, backend: str):
    """Serve through the normal entry point; returns the engine and the
    jitted steps it ran."""
    import numpy as np
    from repro.launch.serve import serve
    out = serve(cfg, batch=SLOTS, prompt_len=PROMPT, gen_len=GEN,
                temperature=0.0, max_slots=SLOTS, backend=backend, seed=SEED)
    eng, rep = out["engine"], out["report"]
    check(len(rep["requests"]) == SLOTS,
          f"{len(rep['requests'])} requests reported, {SLOTS} submitted")
    for r in rep["requests"]:
        toks = np.asarray(r["tokens"])
        check(r["status"] == "finished" and toks.shape == (GEN,),
              f"request {r['rid']}: status {r['status']}, "
              f"tokens {toks.shape}")
        check(bool((toks >= 0).all() and (toks < cfg.vocab).all()),
              f"request {r['rid']}: token outside the vocabulary")
    s = rep["summary"]
    log(f"served {int(s['requests'])} requests x {GEN} tokens on "
        f"{eng.engine.backend}: page {eng.page_size}, chunk "
        f"{eng.prefill_chunk}, {int(s['prefill_chunks'])} prefill chunks, "
        f"{int(s['preemptions'])} preemptions")
    log(f"observed, compilation included: TTFT p50 {s['p50_ttft_s']} s / "
        f"p99 {s['p99_ttft_s']} s, ITL p50 {s['p50_itl_s']} s / "
        f"p95 {s['p95_itl_s']} s, {s['tokens_per_s']} tokens/s")
    ran = sorted(k for k, v in eng.jit_cache_stats().items() if v)
    log(f"jitted steps run: {ran}")
    check({"prefill_nl", "chunk_nl", "chunk", "decode"} <= set(ran),
          f"chunked prefill and decode did not both run: {ran}")
    return eng, ran


def _fresh_state(eng):
    from repro.models import transformer as tf
    return tf.init_paged_state(eng.model_cfg, eng.max_slots,
                               eng.alloc.n_pages, eng.page_size,
                               eng.max_pages_per_seq,
                               dtype=eng.model_cfg.dtype)


def _step_args(eng, which: str, state, prompt):
    """The arguments the engine gives step ``which`` for a request in slot
    0 whose table row holds pages 0..MP-1."""
    import jax.numpy as jnp
    import numpy as np
    chunk = eng.prefill_chunk
    row = jnp.asarray(np.arange(eng.max_pages_per_seq, dtype=np.int32))
    slot = jnp.int32(0)
    if which in ("prefill", "prefill_nl"):
        return (eng.params, jnp.asarray(prompt[None, :chunk]), state, slot,
                row)
    if which in ("chunk", "chunk_nl"):
        # the scheduler's static bound: pages the padded prompt occupies
        kv_pages = min(eng.max_pages_per_seq, -(-PROMPT // eng.page_size))
        return (eng.params, jnp.asarray(prompt[None, chunk:2 * chunk]),
                state, slot, row, jnp.int32(chunk), kv_pages)
    tok = np.zeros((eng.max_slots, 1), np.int32)
    tok[0, 0] = prompt[2 * chunk]
    active = np.zeros((eng.max_slots,), bool)
    active[0] = True
    return (eng.params, jnp.asarray(tok), state, jnp.asarray(active))


def kernel_counts(eng, steps, prompt):
    """``tpu_custom_call`` count in the compiled HLO of each step."""
    from repro.serving.engine import _jitted_steps
    fns = _jitted_steps(eng.engine, eng.model_cfg, eng.page_size)
    counts = {}
    for which in steps:
        text = fns[which].lower(
            *_step_args(eng, which, eng.state, prompt)).compile().as_text()
        counts[which] = text.count('custom_call_target="tpu_custom_call"')
    log(f"tpu_custom_call ops per compiled step: {counts}")
    empty = [w for w, n in counts.items() if n == 0]
    check(not empty, f"steps with no Mosaic kernel: {empty}")
    return counts


def compare_phase(eng, prompt):
    """Prefill (first chunk), the next chunk and one decode step of one
    fresh request on the engine's context and on the xla context, from
    the same params and state; returns the relative L2 distances."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import _jitted_steps
    chunk = eng.prefill_chunk
    logits = {}
    for ctx in (eng.engine, eng.engine.with_backend("xla")):
        fns = _jitted_steps(ctx, eng.model_cfg, eng.page_size)
        st = _fresh_state(eng)
        l0, st = fns["prefill"](*_step_args(eng, "prefill", st, prompt))
        l1, st = fns["chunk"](*_step_args(eng, "chunk", st, prompt))
        row = jnp.asarray(np.arange(eng.max_pages_per_seq, dtype=np.int32))
        st = st._replace(tables=st.tables.at[0].set(row),
                         lengths=st.lengths.at[0].set(2 * chunk))
        l2, st = fns["decode"](*_step_args(eng, "decode", st, prompt))
        logits[ctx.backend] = [np.asarray(l0[0], np.float32),
                               np.asarray(l1[0], np.float32),
                               np.asarray(l2[0, -1], np.float32)]
        del st, l0, l1, l2
    errs = {}
    for name, a, b in zip(("prefill", "chunk", "decode"),
                          logits[eng.engine.backend], logits["xla"]):
        check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
              f"{name}: non-finite logits")
        errs[name] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
        log(f"{name} logits {a.shape}: {eng.engine.backend} vs xla "
            f"relative L2 {errs[name]:.3e} (limit {LOGIT_RTOL}), "
            f"max |diff| {float(np.abs(a - b).max()):.3e}, argmax "
            f"agreement {agree:.3f}")
    bad = {k: v for k, v in errs.items() if not v <= LOGIT_RTOL}
    check(not bad, f"logits differ beyond {LOGIT_RTOL}: {bad}")
    return errs


def one_chip(cfg) -> None:
    import numpy as np
    eng, ran = serve_phase(cfg, "pallas")
    log(f"device bytes after serving: {device_memory()}")
    # request 0's prompt, drawn as serve() draws it
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab, (PROMPT,)).astype(np.int32)
    kernel_counts(eng, sorted(set(ran) | {"prefill"}), prompt)
    eng.state = None                 # the run is over: free its page arena
    gc.collect()
    compare_phase(eng, prompt)


# ---------------------------------------------------------------------------
# four chips: the trainer's mesh
# ---------------------------------------------------------------------------
def train_phase(cfg) -> None:
    from repro.launch import train
    losses = {}
    for backend in ("pallas", "xla"):
        targs = train.parse_args([
            "--arch", ARCH, "--steps", str(TRAIN_STEPS),
            "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
            "--seed", str(SEED), "--log-every", "1", "--backend", backend])
        res = train.train_once(targs, cfg, pods=1)
        check(res.steps_done == TRAIN_STEPS,
              f"{backend}: {res.steps_done} of {TRAIN_STEPS} steps")
        losses[backend] = res.losses
        log(f"{backend} losses {res.losses}")
        # the final train state is still alive here: its bytes per device
        log(f"{backend} device bytes: {device_memory()}")
        del res
        gc.collect()
    diffs = [abs(a - b) for a, b in zip(losses["pallas"], losses["xla"])]
    log(f"|pallas - xla| loss per step: {diffs} (limit {LOSS_ATOL})")
    check(all(d <= LOSS_ATOL for d in diffs),
          f"train losses differ beyond {LOSS_ATOL}: {diffs}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving engine on one chip; 4: train "
                         "steps on the (data, model) mesh of four chips")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import platform
    log(f"compile cache: {platform.use_compile_cache()}")
    os.environ["GEMMINI_TUNE"] = "off"
    from repro import configs
    from repro.core import flags
    flags.set_flag("tune_mode", "off")
    cfg = configs.get(ARCH)
    log(platform.device_banner("pallas"))
    if args.chips == 1:
        one_chip(cfg)
    else:
        train_phase(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
