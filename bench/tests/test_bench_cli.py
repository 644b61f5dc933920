"""The command refuses to run without a chip, or without the program, and
then prints no result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench.harness import BENCH, ROOT


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-serve-chat",
         "--seed", str(2 ** 31 + 99), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)
    return p


def _no_result(stdout: str) -> bool:
    lines = [x for x in stdout.splitlines() if x.strip()]
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_no_accelerator_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert not (tmp_path / ".jax_cache").exists()


def test_unknown_workload_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and _no_result(p.stdout)
