"""Roofline table: aggregates the dry run's results/dryrun/*.json
(``python -m repro.launch.dryrun``) into one row per arch x shape x mesh x
variant. The terms are compile-time cost-analysis estimates, not
timings."""

from __future__ import annotations

import glob
import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def _backfill_fraction(r):
    """Rows saved before min_bytes landed: recompute roofline_fraction with
    the memory-aware ideal (max of compute and inherent-bytes roofs)."""
    if "t_ideal" in r:
        return r
    from repro import configs
    from repro.analysis import roofline as rl
    from repro.launch.steps import SHAPES
    cfg = configs.get(r["arch"])
    info = SHAPES[r["shape"]]
    n_chips = 512 if r["mesh"] == "2x16x16" else 256
    mb = rl.model_min_bytes_for(cfg, info["kind"], info["batch"],
                                info["seq"])
    t_bound = max(r["t_compute"], r["t_memory"], r["t_collective"])
    chip = rl.peaks(rl.DRYRUN_DEVICE_KIND)
    t_ideal = max(float(r["model_flops"]) / n_chips / chip.flops_bf16,
                  mb / n_chips / chip.hbm_bw)
    r["min_bytes"] = mb
    r["t_ideal"] = t_ideal
    r["roofline_fraction"] = t_ideal / t_bound if t_bound else 0.0
    return r


def rows(variant=None):
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if variant and r.get("variant") != variant:
            continue
        out.append(_backfill_fraction(r))
    return out


def fmt_ms(s):
    return f"{float(s) * 1e3:.1f}"


def main(csv=True):
    rs = rows()
    if csv:
        print("# bench_roofline: dry-run roofline terms per cell")
        print("variant,arch,shape,mesh,kind,t_compute_ms,t_memory_ms,"
              "t_collective_ms,bottleneck,useful_ratio,roofline_fraction,"
              "hbm_gb_per_dev")
        for r in rs:
            print(f"{r.get('variant','baseline')},{r['arch']},{r['shape']},"
                  f"{r['mesh']},{r.get('kind','?')},"
                  f"{fmt_ms(r['t_compute'])},{fmt_ms(r['t_memory'])},"
                  f"{fmt_ms(r['t_collective'])},{r['bottleneck']},"
                  f"{float(r['useful_ratio']):.3f},"
                  f"{float(r['roofline_fraction']):.4f},"
                  f"{float(r['per_device_hbm'])/1e9:.2f}")
    return rs


if __name__ == "__main__":
    main()
