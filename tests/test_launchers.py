"""End-to-end launcher integration: train (with failure injection +
checkpoint restart) and serve, run as real CLI subprocesses."""

import os
import subprocess
import sys

import pytest

from tests.conftest import REPO, SRC


def _run(args, timeout=480):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-m"] + args, env=env,
                       capture_output=True, text=True, timeout=timeout,
                       cwd=REPO)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


@pytest.mark.slow
def test_train_with_failure_and_restart(tmp_path):
    out = _run(["repro.launch.train", "--arch", "gemma3-1b", "--smoke",
                "--steps", "10", "--batch", "4", "--seq", "64",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "4",
                "--log-every", "5", "--fail-at", "6"])
    assert "FAILURE (attempt 0): injected failure at step 6" in out
    assert "restored checkpoint step=4" in out
    assert "done: 10 steps" in out
    assert "attempts=2" in out


@pytest.mark.slow
def test_train_moe_arch(tmp_path):
    out = _run(["repro.launch.train", "--arch", "granite-moe-3b-a800m",
                "--smoke", "--steps", "4", "--batch", "4", "--seq", "32",
                "--log-every", "2"])
    assert "done: 4 steps" in out
    assert out.splitlines()[0].startswith("[train] platform=cpu ")
    assert "backend=xla" in out.splitlines()[0]


def test_serve_ssm(tmp_path):
    out = _run(["repro.launch.serve", "--arch", "mamba2-1.3b", "--smoke",
                "--batch", "2", "--prompt-len", "16", "--gen", "4"])
    assert "out shape (2, 4)" in out
    # the first line names the device and the backend chosen
    assert out.splitlines()[0].startswith("[serve] platform=cpu ")
    assert "backend=xla" in out.splitlines()[0]


def test_serve_profile_writes_xplane(tmp_path):
    """``serve --profile DIR``: one profiler session over the serving run,
    with the engine's phase spans in it."""
    out = _run(["repro.launch.serve", "--arch", "gemma3-1b", "--smoke",
                "--batch", "2", "--prompt-len", "8", "--gen", "3",
                "--profile", str(tmp_path)])
    assert "out shape (2, 3)" in out
    path, = tmp_path.rglob("*.xplane.pb")
    assert b"engine.step" in path.read_bytes()


def test_serve_multicodebook(tmp_path):
    out = _run(["repro.launch.serve", "--arch", "musicgen-medium",
                "--smoke", "--batch", "2", "--prompt-len", "8",
                "--gen", "3"])
    assert "out shape (2, 3, 4)" in out


_CACHE_PROBE = """
import os, sys, jax, jax.numpy as jnp
from repro.launch import platform
where = platform.use_compile_cache()
assert jax.config.jax_compilation_cache_dir == where, (
    jax.config.jax_compilation_cache_dir, where)
print(where)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """$JAX_COMPILATION_CACHE_DIR wins and receives the entries; without
    it the cache is the fixed .jax_cache/ at the checkout root."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path)
    assert p.returncode == 0, p.stderr
    where = p.stdout.strip().splitlines()[-1]
    if from_env:
        assert where == str(tmp_path / "cache")
        assert any((tmp_path / "cache").iterdir())
    else:
        assert where == os.path.join(REPO, ".jax_cache")
