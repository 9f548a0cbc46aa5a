import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the production mesh, the sharded
ShapeDtypeStruct inputs, jits the right step (train / prefill / serve),
``.lower().compile()``s it, prints ``memory_analysis()`` /
``cost_analysis()``, and dumps the roofline terms to a JSON results file
that ``python -m paper.run`` tabulates (paper/bench_roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b \
      --shape train_4k --multi-pod            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all               # 1-pod cells
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod   # 2-pod cells
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.analysis import roofline
from repro.core.config import GemminiConfig
from repro.core.generator import elaborate
from repro.launch import steps as steps_lib
from repro.launch.mesh import activate_mesh, make_production_mesh
from repro.optim import adamw

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def _engine():
    return elaborate(GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                                   output_dtype="bf16"), "xla")


def run_cell(arch: str, shape: str, multi_pod: bool, *, verbose: bool = True,
             variant: str = "baseline"):
    cfg = configs.get(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    engine = _engine()
    spec = steps_lib.input_specs(cfg, shape, mesh)
    kind = spec["kind"]

    with activate_mesh(mesh):
        if kind == "train":
            fn = steps_lib.make_train_step(
                engine, cfg, adamw.AdamWConfig(), mesh,
                batch=spec["batch"], seq=spec["seq"])
        elif kind == "prefill":
            fn = steps_lib.make_prefill_step(engine, cfg, mesh,
                                             batch=spec["batch"],
                                             seq=spec["seq"])
        else:
            fn = steps_lib.make_serve_step(engine, cfg, mesh,
                                           batch=spec["batch"],
                                           max_seq=spec["seq"])
        t0 = time.time()
        lowered = jax.jit(fn).lower(*spec["args"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mf = roofline.model_flops_for(cfg, kind, spec["batch"], spec["seq"])
    rl = roofline.analyze(compiled, None, arch=arch, shape=shape,
                          mesh_name=mesh_name, n_chips=n_chips,
                          model_flops=mf)
    rl.min_bytes = roofline.model_min_bytes_for(cfg, kind, spec["batch"],
                                                spec["seq"])
    row = rl.row()
    row.update(lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
               kind=kind, variant=variant)
    ma = compiled.memory_analysis()
    row["memory_analysis"] = dict(
        argument_size=ma.argument_size_in_bytes,
        output_size=ma.output_size_in_bytes,
        temp_size=ma.temp_size_in_bytes,
        generated_code_size=ma.generated_code_size_in_bytes)
    if verbose:
        print(f"[{arch} x {shape} x {mesh_name}] kind={kind}")
        print(f"  lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/1e9:.2f}GB"
              f" out={ma.output_size_in_bytes/1e9:.2f}GB"
              f" temp={ma.temp_size_in_bytes/1e9:.2f}GB per device")
        print(f"  cost_analysis: flops/dev={rl.flops:.3e}"
              f" bytes/dev={rl.hbm_bytes:.3e}")
        print(f"  roofline: compute={rl.t_compute*1e3:.2f}ms"
              f" memory={rl.t_memory*1e3:.2f}ms"
              f" collective={rl.t_collective*1e3:.2f}ms"
              f" -> {rl.bottleneck}-bound"
              f" useful={rl.useful_ratio:.2f}"
              f" roofline_frac={rl.roofline_fraction:.3f}")
    return row


def save_row(row, outdir: str):
    os.makedirs(outdir, exist_ok=True)
    name = f"{row['variant']}_{row['arch']}_{row['shape']}_{row['mesh']}.json"
    with open(os.path.join(outdir, name), "w") as f:
        json.dump(row, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--opt", action="append", default=[],
                    help="optimization flag name[=value] (repeatable); "
                         "see repro.core.flags")
    ap.add_argument("--outdir", default=os.path.abspath(RESULTS))
    args = ap.parse_args()

    from repro.core import flags
    for spec in args.opt:
        flags.parse_opt(spec)

    cells = []
    if args.all:
        for arch in configs.names():
            for shape in configs.shapes_for(arch):
                cells.append((arch, shape))
    else:
        shapes = [args.shape] if args.shape else configs.shapes_for(args.arch)
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for arch, shape in cells:
        try:
            row = run_cell(arch, shape, args.multi_pod,
                           variant=args.variant)
            save_row(row, args.outdir)
        except Exception as e:  # noqa
            failures.append((arch, shape, repr(e)))
            print(f"[FAIL {arch} x {shape}]")
            traceback.print_exc()
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells OK")
    for f in failures:
        print("FAILED:", f)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
