"""Serving driver over the continuous-batching engine.

Requests run through ``repro.serving.ServingEngine``: paged KV cache,
admission queue, prefill/decode interleaving, preemption under cache
pressure -- the request-level system layer (docs/serving.md). The old
static batch loop survives as ``--policy static`` (admission barrier, no
slot recycling) for A/B comparison. Speed is measured on the chip by
``bench/run.py`` (PERF.md), not by this CLI.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke \
      --batch 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro import configs
from repro.core import flags
from repro.core.generator import default_engine_backend
from repro.launch import platform
from repro.serving import ServingEngine


def serve(model_cfg, *, batch: int, prompt_len: int, gen_len: int,
          temperature: float = 1.0, seed: int = 0, eos_id: int = -1,
          policy: str = "continuous", max_slots: int = 0,
          page_size: int = 0, prefill_chunk: int = 0,
          backend: str = "", admission_policy: str = "fifo",
          faults: str = "", enforce_deadlines: bool = False,
          deadline_s: float = 0.0, trace=None,
          kv_offload: bool = False, prefix_cache: bool = False,
          host_pool_pages: int = 0):
    """Serve ``batch`` random-prompt requests; returns the old static-loop
    schema (tokens (B, gen[, n_q]), t_prefill, t_decode, tok_per_s) plus
    the engine's full telemetry under ``report``.

    ``prefill_chunk``: chunked-prefill granularity in cache positions --
    0 = one page (the default: page-multiple chunks keep chunk boundaries
    page-aligned), negative = disabled (single-pass prefill).
    ``backend``: the engine ``ExecutionContext`` backend (empty = host
    default: pallas on TPU, xla elsewhere); ``admission_policy``:
    fifo | priority | deadline (scheduler admission order).

    Robustness knobs (docs/serving.md#robustness): ``faults`` is a
    ``GEMMINI_FAULTS``-grammar spec string (empty = env/off);
    ``enforce_deadlines`` sheds expired requests instead of serving
    them; ``deadline_s`` stamps every submitted request with a relative
    per-request SLO (0 = best-effort).

    KV-lifecycle knobs (docs/serving.md#kv-lifecycle): ``kv_offload``
    spills preemption victims to a host pool (``host_pool_pages`` deep,
    0 = arena-sized) so restart is a restore instead of a recompute;
    ``prefix_cache`` maps shared prompt prefixes copy-on-write. Both off
    by default and bit-exact either way.

    ``trace`` follows ``ServingEngine(trace=)``: None consults
    ``$GEMMINI_TRACE``, True/int/Tracer turns span tracing on for this
    run (docs/observability.md). The engine's tracer is also installed
    process-globally for the duration so tuner-measurement and
    fault-injection spans land on the same timeline."""
    rng = np.random.default_rng(seed)
    max_slots = max_slots or min(batch, 8)
    max_context = prompt_len + model_cfg.n_meta_tokens + gen_len + 64
    engine = ServingEngine(
        model_cfg, max_slots=max_slots, max_context=max_context,
        page_size=page_size or None, seed=seed, temperature=temperature,
        policy=policy, warm_prompt_lens=[prompt_len],
        prefill_chunk=None if prefill_chunk < 0 else prefill_chunk,
        backend=backend or None, admission_policy=admission_policy,
        faults=faults or None, enforce_deadlines=enforce_deadlines,
        trace=trace, kv_offload=kv_offload, prefix_cache=prefix_cache,
        host_pool_pages=host_pool_pages or None)
    if engine.tracer is not None:
        from repro.obs import trace as otrace
        otrace.install(engine.tracer)
    if engine.warm_stats is not None:
        from repro import tune
        s = engine.warm_stats
        print(f"[serve] plan warmup ({flags.get('tune_mode')}): "
              f"{s['gemm_shapes']} gemm + {s['attn_shapes']} attn + "
              f"{s['paged_shapes']} paged shapes, {s['cache_hits']} cache "
              f"hits, {s['cache_misses']} misses "
              f"(cache: {tune.default_cache_path()})")
        print(f"[serve] paged cache: page={engine.page_size} tokens, "
              f"arena={engine.alloc.n_pages} pages")

    tok_shape = (prompt_len, model_cfg.n_codebooks) \
        if model_cfg.n_codebooks > 1 else (prompt_len,)
    # Deadlines are absolute timestamps on the ENGINE clock (monotonic by
    # default -- wall clocks step under NTP), so derive from engine.now().
    deadline = (engine.now() + deadline_s) if deadline_s > 0 else None
    for _ in range(batch):
        prompt = rng.integers(0, model_cfg.vocab, tok_shape).astype(np.int32)
        engine.submit(prompt, gen_len, eos_id=eos_id, deadline=deadline)
    t0 = time.time()
    try:
        report = engine.run()
    finally:
        if engine.tracer is not None:
            from repro.obs import trace as otrace
            if otrace.active() is engine.tracer:
                otrace.deactivate()
    wall = time.time() - t0

    # Old static-loop output schema: (B, gen) tokens, frozen-at-0 past EOS
    # (shed requests contribute their exact partial stream, zero-padded).
    full_shape = (gen_len, model_cfg.n_codebooks) \
        if model_cfg.n_codebooks > 1 else (gen_len,)
    outs = []
    for r in report["requests"]:
        toks = np.asarray(r["tokens"], np.int32).reshape(
            (-1,) + full_shape[1:])
        pad_shape = (gen_len - toks.shape[0],) + toks.shape[1:]
        outs.append(np.concatenate([toks, np.zeros(pad_shape, np.int32)]))
    toks = np.stack(outs)
    summ = report["summary"]
    ttft = max(r["ttft_s"] or 0.0 for r in report["requests"])
    return dict(tokens=toks, t_prefill=ttft, t_decode=wall - ttft,
                tok_per_s=summ["tokens_per_s"], report=report,
                engine=engine)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--policy", choices=("continuous", "static"),
                    default="continuous",
                    help="continuous batching (default) or the static "
                         "group-barrier baseline")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (default: min(batch, 8))")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size (default: tuned or 64)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill granularity in tokens (cache "
                         "positions per chunk, interleaved with decode "
                         "steps). Default 0 = one page; negative disables "
                         "chunking (single-pass prefill)")
    ap.add_argument("--tune", choices=flags.TUNE_MODES, default=None,
                    help="tile-plan autotuning mode (default: $GEMMINI_TUNE)")
    ap.add_argument("--backend", choices=("xla", "pallas", "interpret"),
                    default="",
                    help="engine ExecutionContext backend (default: pallas "
                         "on TPU hosts, xla elsewhere)")
    ap.add_argument("--admission", choices=("fifo", "priority", "deadline"),
                    default="fifo",
                    help="scheduler admission order (priority/deadline use "
                         "Request.priority / Request.deadline)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault-injection spec "
                         "(GEMMINI_FAULTS grammar, e.g. "
                         "'seed=7;nan@decode:p=0.2,max=2'); empty = "
                         "$GEMMINI_FAULTS / off")
    ap.add_argument("--enforce-deadlines", action="store_true",
                    help="shed requests whose deadline passed "
                         "(terminal deadline_missed status) instead of "
                         "serving them to completion")
    ap.add_argument("--deadline", type=float, default=0.0, metavar="S",
                    help="per-request SLO: stamp every request with "
                         "submit-time + S seconds (0 = best-effort)")
    ap.add_argument("--kv-offload", action="store_true",
                    help="spill preemption victims' committed KV pages to "
                         "a host pool so restart is a DMA restore instead "
                         "of a full re-prefill (docs/serving.md#kv-lifecycle)")
    ap.add_argument("--host-pool-pages", type=int, default=0,
                    help="host offload pool capacity in pages "
                         "(0 = arena-sized; only with --kv-offload)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hash full KV pages at prefill commit and "
                         "map shared prompt prefixes copy-on-write "
                         "(attention-only families)")
    ap.add_argument("--trace", action="store_true",
                    help="record request/engine/allocator/tuner spans and "
                         "export a Chrome-trace JSON (see --trace-out); "
                         "off by default, also togglable via $GEMMINI_TRACE")
    ap.add_argument("--trace-out", default="TRACE_serve.json", metavar="PATH",
                    help="Chrome-trace output path for --trace "
                         "(default: TRACE_serve.json; load in "
                         "chrome://tracing or ui.perfetto.dev, or summarize "
                         "with python -m repro.obs PATH)")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="record a jax.profiler session over the serving "
                         "run into DIR: an .xplane.pb with the device ops "
                         "and the engine's phase spans on one clock "
                         "(open in TensorBoard's profiler or Perfetto)")
    args = ap.parse_args(argv)
    platform.use_compile_cache()
    backend = args.backend or default_engine_backend()
    print(f"[serve] {platform.device_banner(backend)}")
    # Always re-set: set_flag validates, so a typo'd $GEMMINI_TUNE fails at
    # startup instead of (maybe never) at the first plan resolution.
    flags.set_flag("tune_mode", args.tune if args.tune is not None
                   else flags.get("tune_mode"))
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    import contextlib
    run_ctx = contextlib.nullcontext()
    if args.profile:
        import jax
        run_ctx = jax.profiler.trace(args.profile)
    with run_ctx:
        out = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                    gen_len=args.gen, temperature=args.temperature,
                    policy=args.policy, max_slots=args.slots,
                    page_size=args.page_size,
                    prefill_chunk=args.prefill_chunk,
                    backend=backend,
                    admission_policy=args.admission,
                    faults=args.faults,
                    enforce_deadlines=args.enforce_deadlines,
                    deadline_s=args.deadline,
                    trace=True if args.trace else None,
                    kv_offload=args.kv_offload,
                    prefix_cache=args.prefix_cache,
                    host_pool_pages=args.host_pool_pages)
    if args.profile:
        print(f"[serve] profile: {args.profile} (device ops and engine.* "
              f"spans; read with jax.profiler.ProfileData.from_file)")
    s = out["report"]["summary"]

    def ms(v):
        # Percentiles are None (JSON null) for empty populations.
        return "n/a" if v is None else f"{v * 1e3:.0f}ms"

    print(f"[serve] {args.policy}: {int(s['requests'])} reqs, "
          f"{int(s['new_tokens'])} tokens in {s['wall_s']*1e3:.0f}ms "
          f"({out['tok_per_s']:.1f} tok/s), "
          f"p50 latency {ms(s['p50_latency_s'])}, "
          f"p99 {ms(s['p99_latency_s'])}, "
          f"ITL p50 {ms(s['p50_itl_s'])} / p95 {ms(s['p95_itl_s'])}, "
          f"{int(s['prefill_chunks'])} prefill chunks, "
          f"preemptions {int(s['preemptions'])}, "
          f"out shape {out['tokens'].shape}")
    if s["injected_faults"] or s["retries"] or s["fallbacks"] or s["shed"]:
        faults_seen = out["report"].get("faults", {})
        print(f"[serve] robustness: {int(s['injected_faults'])} injected "
              f"({faults_seen}), {int(s['retries'])} retries, "
              f"{int(s['fallbacks'])} xla fallbacks, "
              f"{int(s['shed'])} shed, "
              f"{int(s['straggler_steps'])} straggler steps, "
              f"quarantined {out['report']['quarantined'] or 'none'}")
    if args.kv_offload or args.prefix_cache:
        print(f"[serve] kv-lifecycle: "
              f"{int(s['prefill_tokens'])} prefill tokens computed, "
              f"{int(s['prefix_hit_tokens'])} prefix-hit (skipped), "
              f"{int(s['offload_spills'])} spills / "
              f"{int(s['offload_restores'])} restores, restarts "
              f"{int(s['restarts_restored'])} restored / "
              f"{int(s['restarts_recomputed'])} recomputed")
    tracer = out["engine"].tracer
    if tracer is not None and args.trace:
        tracer.export_chrome(args.trace_out)
        print(f"[serve] trace: {len(tracer.events)} events "
              f"({tracer.dropped} dropped) -> {args.trace_out} "
              f"(summarize: python -m repro.obs {args.trace_out})")
    return out


if __name__ == "__main__":
    main()
