"""Without a TPU, or without the program, a run prints no result."""
import os
import shutil
import subprocess
import sys

from bench import run

CMD = [sys.executable, "bench/run.py", "--workload", "glove100-sq8.closed",
       "--seed", "3000000001", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_run_refuses_without_result():
    out = _run(run.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_alone_refuses_without_result(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
