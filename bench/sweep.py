#!/usr/bin/env python3
"""Find a cell's knee: the highest arrival rate its system sustains.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 1,1.5,2,3

One set-up, then for each rate an open-loop window of ``--seconds`` at that
rate with the cell's own lengths, then a drain. For each rate it prints the
requests due and finished in the window, the queue at its close, the
output tokens per second, and the TTFT and ITL tails. A rate the system
sustains finishes about what it was offered and ends with a short queue;
above the knee the queue grows all through the window. Used once, when a
cell is defined, to fix the rate in its traffic file: a run of the
benchmark never searches for a rate.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    os.environ["GEMMINI_TUNE"] = "off"
    from bench import harness, traffic
    cell = harness.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[sweep] no TPU", file=sys.stderr)
        return 2
    from repro.core import flags
    from repro.launch import platform
    platform.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    flags.set_flag("tune_mode", "off")
    mc = harness.model_config(cell)
    params = harness.make_params(cell, args.seed)
    engine = harness.make_engine(cell, mc, params)
    harness.warm_up(engine, args.seed)
    print(f"[sweep] set-up {time.monotonic() - T_START:.1f} s",
          file=sys.stderr, flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate, kind="open")
        items = traffic.generate(mix, args.seed, args.seconds, mc.vocab)
        served, t0, t1 = harness.run_open(
            engine, items, args.seconds, engine.now,
            harness.Hooks(engine, args.seconds, None))
        queue = len(engine.sched.queue)
        run = harness.Run(cell, args.seconds, t0, t1, served, engine)
        win = run.window_due
        done = sum(1 for s in win if s.req.t_finished is not None
                   and s.req.t_finished <= t1)
        row = {"rate_rps": rate, "due": len(win), "finished_in_window": done,
               "queue_at_close": queue}
        row["ttft_p90_s"] = harness.percentile(
            [s.req.t_first_token - s.due if s.req.t_first_token is not None
             else float("inf") for s in win], 90)
        for name in ("itl_p95_ms", "out_tok_s"):
            row[name] = harness.metric_reader(name).read(run)
        print(json.dumps(row), flush=True)
        while engine.sched.has_work:
            engine.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
