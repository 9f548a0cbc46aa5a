"""Roofline terms from a compiled dry-run artifact.

Three terms per (arch x shape x mesh), per the assignment formulas:

  compute    = HLO_FLOPs / peak_FLOPs_per_chip
  memory     = HLO_bytes / HBM_bw_per_chip
  collective = collective_bytes / link_bw_per_chip

All three inputs come from :mod:`repro.analysis.hlo`, a loop-aware static
analysis of the SPMD-partitioned compiled module. We do NOT use
``compiled.cost_analysis()`` for the terms because XLA counts while-loop
bodies once instead of x trip_count (verified empirically; every model here
scans over layers, so the builtin numbers under-count by ~n_layers). The
builtin numbers are still recorded as ``xla_flops`` / ``xla_bytes`` for
cross-checking. Shapes in the partitioned module are per-device, so all
terms divide by single-chip peaks.

collective_bytes sums the output-shape bytes of all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute ops (x execution
multiplier). all-reduce is counted x2: its torus lowering is
reduce-scatter + all-gather, each moving the full buffer.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator generation."""

    flops_bf16: float             # FLOP/s
    ops_int8: float               # OP/s
    hbm_bw: float                 # B/s
    ici_bw_per_link: float        # B/s per link


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, ops_int8=393e12,
                             hbm_bw=819e9, ici_bw_per_link=50e9),
}

# The chip the dry-run meshes (launch/mesh.py) describe.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; a device without published
    peaks (the CPU, an unlisted TPU) raises instead of borrowing another
    chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. "f32[256,8192]{1,0} all-reduce(" and tuple-shaped variants
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9_]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind output bytes of every collective in the HLO module.

    ``-done`` ops are skipped (their ``-start`` counterpart was counted).
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done(" in line:
            continue
        kind = m.group(2)
        out[kind] += _shape_bytes(m.group(1))
        counts[kind] += 1
    out_tot = dict(out)
    out_tot["_counts"] = counts  # type: ignore
    return out_tot


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device HLO bytes accessed
    coll_bytes: float             # per-device collective bytes (AR x2)
    coll_breakdown: Dict[str, int]
    per_device_hbm_peak: float    # memory_analysis: args+outs+temps
    model_flops: float            # 6ND / 2ND analytic useful flops (global)
    n_chips: int
    xla_flops: float = 0.0        # builtin cost_analysis (loop bodies x1)
    xla_bytes: float = 0.0        # kept for cross-checking only
    min_bytes: float = 0.0        # inherent minimal HBM traffic (global)

    @property
    def chip(self) -> ChipPeaks:
        return peaks(DRYRUN_DEVICE_KIND)

    @property
    def t_compute(self) -> float:
        return self.flops / self.chip.flops_bf16

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.chip.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.chip.ici_bw_per_link

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (global HLO flops)."""
        total = self.flops * self.n_chips
        return self.model_flops / total if total else 0.0

    @property
    def t_ideal(self) -> float:
        """The roofline lower bound for this workload on this machine:
        the larger of (useful compute at peak) and (inherent minimal HBM
        traffic at full bandwidth). Decode steps are intrinsically
        memory-bound -- every parameter and cache byte must be read once
        per token -- so their roof is the memory term, not compute."""
        t_c = self.model_flops / self.n_chips / self.chip.flops_bf16
        t_m = self.min_bytes / self.n_chips / self.chip.hbm_bw
        return max(t_c, t_m)

    @property
    def roofline_fraction(self) -> float:
        """t_ideal / t_bound: what fraction of the workload's own roofline
        the compiled step achieves."""
        return self.t_ideal / self.t_bound if self.t_bound else 0.0

    def row(self) -> Dict:
        return dict(
            arch=self.arch, shape=self.shape, mesh=self.mesh,
            t_compute=self.t_compute, t_memory=self.t_memory,
            t_collective=self.t_collective, bottleneck=self.bottleneck,
            hlo_flops=self.flops, hlo_bytes=self.hbm_bytes,
            coll_bytes=self.coll_bytes,
            model_flops=self.model_flops, useful_ratio=self.useful_ratio,
            roofline_fraction=self.roofline_fraction,
            per_device_hbm=self.per_device_hbm_peak,
            xla_flops=self.xla_flops, xla_bytes=self.xla_bytes,
            min_bytes=self.min_bytes, t_ideal=self.t_ideal,
            coll_breakdown={k: v for k, v in self.coll_breakdown.items()
                            if k != "_counts" and v},
        )


def analyze(compiled, lowered_text: Optional[str], *, arch: str, shape: str,
            mesh_name: str, n_chips: int, model_flops: float) -> Roofline:
    from repro.analysis import hlo as hlo_lib
    text = lowered_text if lowered_text is not None else compiled.as_text()
    rep = hlo_lib.analyze_hlo(text)
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hbm_peak = float(ma.argument_size_in_bytes + ma.output_size_in_bytes +
                     ma.temp_size_in_bytes) if ma else 0.0
    breakdown = dict(rep.coll_breakdown)
    breakdown["_counts"] = rep.coll_counts  # type: ignore
    rl = Roofline(arch=arch, shape=shape, mesh=mesh_name, flops=rep.flops,
                  hbm_bytes=rep.bytes, coll_bytes=rep.coll_bytes,
                  coll_breakdown=breakdown, per_device_hbm_peak=hbm_peak,
                  model_flops=model_flops, n_chips=n_chips)
    rl.xla_flops = float(ca.get("flops", 0.0))
    rl.xla_bytes = float(ca.get("bytes accessed", 0.0))
    return rl


def model_flops_for(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Analytic useful FLOPs: 6*N*D train, 2*N*D inference forward,
    2*N per decoded token (D = tokens processed, N = active params)."""
    n = cfg.active_param_count()
    if shape_kind == "train":
        return 6.0 * n * batch * seq
    if shape_kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch          # decode: one token per sequence


def model_min_bytes_for(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Inherent minimal global HBM traffic per step (the memory roofline).

    decode:  every active parameter (bf16) and every cache byte must be
             read once per token -- the fundamental decode bound.
    prefill: parameters once + activations written once + KV written.
    train:   parameters + opt state (2x fp32) read/written once + the
             residual stream written in fwd and read in bwd.
    These are deliberate LOWER bounds (no rematerialization, perfect fusion
    of everything else), so roofline_fraction never flatters the system.
    """
    n_active = cfg.active_param_count()
    n_stored = cfg.param_count()
    act_bytes = 2.0 * batch * seq * cfg.d_model          # residual, bf16
    kv_bytes = 0.0
    if cfg.has_attn:
        kv_bytes += (2.0 * cfg.n_layers * batch * seq *
                     cfg.n_kv_heads * cfg.head_dim * 2)  # K+V bf16
    if cfg.has_ssm:
        kv_bytes += (cfg.n_layers * batch * cfg.n_ssm_heads *
                     cfg.d_state * cfg.ssm_head_dim * 4)  # fp32 state
    if shape_kind == "decode":
        return 2.0 * n_active + kv_bytes
    if shape_kind == "prefill":
        return 2.0 * n_active + act_bytes + kv_bytes
    # train: params bf16 + grads bf16 + m/v fp32 r+w, fwd act write + bwd read
    opt_bytes = n_stored * (2 + 2 + 4 * 4)
    return opt_bytes + 2.0 * act_bytes * cfg.n_layers
