#!/usr/bin/env python3
"""The benchmark's one command.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: makes the
weights and the traffic from ``--seed``, warms every compile bucket the
cell can dispatch, serves the traffic through the serving engine for
``--seconds``, checks what the window served against the plain float32
reference, and prints one JSON object as the last line of standard output:
the end-to-end metrics with ``--trace 0``, the per-layer metrics (read from
a device trace of the window) with ``--trace 1``. The last lines of
standard error are each compared number beside its limit.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    os.environ["GEMMINI_TUNE"] = "off"
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"[bench] {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch import platform
    platform.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    for k, v in out["check"].items():
        print(f"[bench] compared {k}: {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
