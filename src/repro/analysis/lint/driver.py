"""Lint driver: instantiate every kernel contract over the tuner's
schedule lattice and run the full static check suite over the repo.

The probe problems below are the smoke-config shapes the serving and
training paths actually launch (small enough to enumerate the whole
lattice in milliseconds, large enough that every grid axis is > 1 so
coverage/race proofs are non-vacuous).  For each (kernel family,
probe), every schedule the tuner would consider
(`tune/schedules.py` / `core/tiling.py`) is checked — this is the same
predicate the tuner's plan-feasibility hook consults, so "the linter
is clean" and "the tuner never measures an infeasible plan" are one
fact.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.config import GemminiConfig
from repro.core import tiling
from repro.tune import schedules
from repro.kernels import contracts as kc
from repro.analysis.lint import checks, source
from repro.analysis.lint.findings import Finding, dedupe

KERNEL_FILES = ("gemm.py", "attention.py", "conv.py", "mamba2.py")

# serving engine's bf16 config + the paper-faithful int8 default
PROBE_CFGS = (
    GemminiConfig(),
    GemminiConfig(input_dtype="bf16", acc_dtype="fp32", output_dtype="bf16"),
)


def _gemm_contracts(cfg: GemminiConfig):
    m = n = k = 512
    for has_bias in (False, True):
        for plan in tiling.enumerate_plans(cfg, m, n, k, has_bias=has_bias,
                                           max_candidates=16):
            inst = (f"m{m}n{n}k{k}t{plan.tile_m}x{plan.tile_n}x"
                    f"{plan.tile_k}{'b' if has_bias else ''}")
            # a plain weight (a stack of one) and a layer scan's stack
            for n_layers in (1, 3):
                yield (kc.gemm_os_contract(cfg, plan, has_bias=has_bias,
                                           n_layers=n_layers),
                       cfg, f"{inst}s{n_layers}")
                yield (kc.gemm_ws_contract(cfg, plan, has_bias=has_bias,
                                           n_layers=n_layers),
                       cfg, f"{inst}s{n_layers}")
            yield (kc.accumulator_epilogue_contract(
                cfg, plan, m=plan.m, n=plan.n), cfg, inst)


def _attn_contracts(cfg: GemminiConfig):
    b, h, kvh, tq, tk, d = 2, 8, 2, 1024, 1024, 128
    in_bytes = 2
    for s in schedules.enumerate_attn_schedules(
            cfg, b, h, kvh, tq, tk, d, in_bytes=in_bytes):
        eff = s.effective(tq, tk)
        inst = f"bq{eff.block_q}bk{eff.block_k}"
        yield (kc.flash_attention_contract(
            cfg, b=b, h=h, kvh=kvh, tq=tq, tk=tk, d=d,
            block_q=eff.block_q, block_k=eff.block_k), cfg, inst)
        yield (kc.decode_attention_contract(
            cfg, b=b, h=h, kvh=kvh, s=tk, d=d, block_k=eff.block_k),
            cfg, inst)


def _paged_contracts(cfg: GemminiConfig):
    b, h, kvh, d, max_context, n_layers = 4, 8, 2, 128, 2048, 2
    for s in schedules.enumerate_paged_schedules(cfg, b, h, kvh, d,
                                                 max_context):
        page = s.effective(max_context).page_size
        mp = -(-max_context // page)
        inst = f"page{page}"
        yield (kc.paged_decode_attention_contract(
            cfg, b=b, h=h, kvh=kvh, d=d, page=page, mp=mp,
            n_pages=b * mp, n_layers=n_layers), cfg, inst)
        yield (kc.paged_prefill_attention_contract(
            cfg, h=h, kvh=kvh, tq=512, d=d, page=page, mp=mp,
            n_pages=b * mp, block_q=512, n_layers=n_layers), cfg, inst)


def _conv_contracts(cfg: GemminiConfig):
    n, h, w, ci, co, khw = 2, 16, 16, 64, 256, 3
    for s in schedules.enumerate_conv_schedules(cfg, n, h, w, ci, co,
                                                khw, khw, padding=1):
        ct = s.effective(co).co_tile
        for has_bias in (False, True):
            yield (kc.conv2d_implicit_contract(
                cfg, n=n, h=h, w=w, ci=ci, co=co, kh=khw, kw=khw,
                co_tile=ct, padding=1, has_bias=has_bias), cfg,
                f"ct{ct}{'b' if has_bias else ''}")


def _ssd_contracts(cfg: GemminiConfig):
    for rfs in (False, True):
        yield (kc.ssd_contract(
            cfg, bsz=2, h=8, nc=4, q=256, p=64, n=64, ngroups=2,
            return_final_state=rfs), cfg, f"fs{int(rfs)}")


def iter_repo_contracts(cfgs: Sequence[GemminiConfig] = PROBE_CFGS):
    for cfg in cfgs:
        yield from _gemm_contracts(cfg)
        yield from _attn_contracts(cfg)
        yield from _paged_contracts(cfg)
        yield from _conv_contracts(cfg)
        yield from _ssd_contracts(cfg)


def run_contract_checks(cfgs: Sequence[GemminiConfig] = PROBE_CFGS
                        ) -> List[Finding]:
    return dedupe(checks.check_all(iter_repo_contracts(cfgs)))


def _kernels_dir() -> Path:
    import repro.kernels as pkg
    return Path(pkg.__file__).parent


def run_source_checks(kernels_dir: Optional[Path] = None) -> List[Finding]:
    kdir = Path(kernels_dir) if kernels_dir else _kernels_dir()
    out: List[Finding] = []
    for name in KERNEL_FILES:
        p = kdir / name
        if p.exists():
            out += source.check_kernel_file(p)
    out += source.check_shim_ban(sorted(kdir.glob("*.py")))
    return dedupe(out)


_CONTRACT_FAMILIES = (
    ("contracts:gemm", _gemm_contracts),
    ("contracts:attn", _attn_contracts),
    ("contracts:paged", _paged_contracts),
    ("contracts:conv", _conv_contracts),
    ("contracts:ssd", _ssd_contracts),
)


def lint_repo_timed(cfgs: Sequence[GemminiConfig] = PROBE_CFGS,
                    kernels_dir: Optional[Path] = None
                    ) -> Tuple[List[Finding], Dict[str, float]]:
    """:func:`lint_repo` plus per-check wall time: one timing bucket per
    contract family and one for the AST source pass, so the JSON report
    shows where the now-multi-pass CI lint job spends its budget.
    Per-family dedupe is equivalent to the global one -- a fingerprint's
    site names its contract family."""
    timings: Dict[str, float] = {}
    out: List[Finding] = []
    for name, gen in _CONTRACT_FAMILIES:
        t0 = time.perf_counter()
        items = []
        for cfg in cfgs:
            items.extend(gen(cfg))
        out += dedupe(checks.check_all(items))
        timings[name] = time.perf_counter() - t0
    t0 = time.perf_counter()
    out += run_source_checks(kernels_dir)
    timings["source"] = time.perf_counter() - t0
    sev = {"error": 0, "warning": 1, "info": 2}
    out = sorted(out, key=lambda f: (sev[f.severity], f.code, f.site))
    return out, timings


def lint_repo(cfgs: Sequence[GemminiConfig] = PROBE_CFGS,
              kernels_dir: Optional[Path] = None) -> List[Finding]:
    """The full static suite: contract checks over the schedule lattice
    plus the AST rules over the kernel sources."""
    return lint_repo_timed(cfgs, kernels_dir)[0]


# re-export for the feasibility hook's lazy import
fits_budgets = checks.fits_budgets
