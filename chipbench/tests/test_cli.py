"""The command refuses to run anywhere but on a TPU, and in a checkout
that holds only the benchmark."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "granite-moe-3b.longctx-tiered", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_exits_non_zero_without_a_tpu():
    p = _run(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout == ""
