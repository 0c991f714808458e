"""The benchmark's entry (`bench/run.py`) refuses to measure without a TPU
or without the program beside it: a non-zero exit and no result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ngp19.fresh800",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    p = run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
