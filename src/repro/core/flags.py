"""Process-wide switches read at trace time.

``tune_mode`` and ``tune_cache`` steer the kernel-schedule
tuner (``repro.tune``); ``moe_grouped_dispatch`` and ``remat_policy``
shape the sharded MoE dispatch and the train step's rematerialization.
The dry-run CLI sets them via ``--opt name[=value]``; tests pin them
explicitly and :func:`reset` restores the defaults.
"""

from __future__ import annotations

import os
from typing import Any, Dict

TUNE_MODES = ("off", "cached", "full")

_DEFAULTS: Dict[str, Any] = {
    # Empirical kernel-schedule autotuner (src/repro/tune), covering all
    # three kernel classes: GEMM tile plans, attention block_q/block_k, and
    # conv co_tile. "off" = static schedules only (greedy analytic GEMM
    # plans, the kernels' shipped block defaults); "cached" = consult the
    # persistent schedule cache, static on a miss (never measures); "full"
    # = measure candidate schedules for unseen shapes and persist the
    # winners. Seeded from $GEMMINI_TUNE so whole-model launchers pick it
    # up without code changes.
    "tune_mode": os.environ.get("GEMMINI_TUNE", "off"),
    # Plan-cache file override; empty = $GEMMINI_TUNE_CACHE, else
    # ~/.cache/gemmini-repro/tile_plans.json (see repro.tune.cache).
    "tune_cache": os.environ.get("GEMMINI_TUNE_CACHE", ""),
    # Group MoE dispatch per data-parallel shard so the scatter-add /
    # gather stay shard-local and the expert regroup lowers to an
    # all-to-all instead of a full-buffer all-reduce.
    "moe_grouped_dispatch": 0,      # truthy = group by the mesh shard grid
    # Activation-rematerialization policy for the train step:
    # "full" (paper-style minimal residency), "dots" (save MXU outputs,
    # recompute elementwise), "none" (save everything).
    "remat_policy": "full",
}

_values: Dict[str, Any] = dict(_DEFAULTS)


def get(name: str) -> Any:
    return _values[name]


def set_flag(name: str, value: Any) -> None:
    if name not in _DEFAULTS:
        raise KeyError(f"unknown flag {name!r}; have {sorted(_DEFAULTS)}")
    if name == "tune_mode" and value not in TUNE_MODES:
        raise ValueError(f"tune_mode must be one of {TUNE_MODES}, got {value!r}")
    _values[name] = value


def reset() -> None:
    _values.clear()
    _values.update(_DEFAULTS)


def parse_opt(spec: str) -> None:
    """``name`` (-> True) or ``name=value`` with int/bool coercion."""
    if "=" in spec:
        name, raw = spec.split("=", 1)
        if raw.lower() in ("true", "false"):
            val: Any = raw.lower() == "true"
        else:
            try:
                val = int(raw)
            except ValueError:
                val = raw
    else:
        name, val = spec, True
    set_flag(name, val)
