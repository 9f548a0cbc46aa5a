#!/usr/bin/env python3
"""Readings that a cell's output limit is set from, on the chip.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, in one process: make the weights, serve the cell's traffic
through the engine for ``--seconds`` at the cell's own load, and judge the
served requests twice with the check a run makes (``check_outputs``: the
same seeded sample, the same limits):

* ``program``: the widest gap by which a served token's logit lies below
  the float32 reference's best, and whether the run is ``correct``;
* ``control``: the same check with the program replaced by the reference
  computed with every matrix product's operands in float8 e4m3 -- the
  precision below the configuration's bfloat16 -- reading, at each served
  position, the gap of the token that the fp8 reference puts first. It
  has to come out not ``correct``.

The limit goes between the largest ``program`` and the smallest
``control`` reading. Runs of the benchmark never run the control.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    os.environ["GEMMINI_TUNE"] = "off"
    from bench import harness, traffic
    cell = harness.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[control] no TPU", file=sys.stderr)
        return 2
    from repro.core import flags
    from repro.launch import platform
    platform.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    flags.set_flag("tune_mode", "off")
    mc = harness.model_config(cell)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        params = harness.make_params(cell, seed)
        engine = harness.make_engine(cell, mc, params)
        if i == 0:
            harness.warm_up(engine, seed)
        items = traffic.generate(cell.mix, seed, args.seconds, mc.vocab)
        hooks = harness.Hooks(engine, args.seconds, None)
        served, _, _ = harness.serve(cell, engine, items, args.seconds, hooks)
        engine.state = engine.params = None
        del engine, hooks
        gc.collect()
        row = {"seed": seed}
        for name, ctrl in (("program", False), ("control", True)):
            ok, cmp = harness.check_outputs(cell, params, served, seed,
                                            control=ctrl)
            row[name] = {"correct": bool(ok), **{
                k: v["value"] for k, v in cmp.items()}}
        row["limit"] = cell.config["check"]["max_logit_gap"]
        row["seconds"] = time.monotonic() - t
        print(json.dumps(row), flush=True)
        del params, served
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
