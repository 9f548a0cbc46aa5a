"""``bench/run.py`` on a machine without a TPU, and in a checkout that
holds only the benchmark: it exits non-zero and prints no result."""

import os
import shutil
import subprocess
import sys

from bench import harness

ROOT = harness.ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen1.5-4b.decode",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_every_name_has_its_files():
    """Each cell's config, traffic and metrics are files found by name."""
    import json
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
        assert (ROOT / c["file"]).with_suffix(".py").exists()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.metrics and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.metrics)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(harness.metric_reader(m["name"]), "read")
