"""What a launcher runs on: the compile cache and the device banner.

Both are called before the first compile by ``chip_smoke.py`` and the
serve/train launchers, so every run names the device it measured and
keeps its compiled programs where the next run looks for them.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/platform.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself. Otherwise the cache goes to ``.jax_cache/`` at the checkout
    root: a fixed path, because the path is part of the cache key and a
    directory that moves between runs never hits."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_banner(backend: str) -> str:
    """One line naming the device JAX found and the engine backend chosen,
    printed first so a run on the CPU cannot pass for a chip run."""
    import jax
    devs = jax.devices()
    return (f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
            f"count={len(devs)} backend={backend}")
