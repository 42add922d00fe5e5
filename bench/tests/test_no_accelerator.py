"""Without a TPU, or without the program, a run fails and prints no
result line."""
import os
import shutil
import subprocess
import sys

from bench import harness


def run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rw1024.seeds8",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def no_result(proc):
    return proc.returncode != 0 and '"correct"' not in proc.stdout


def test_on_the_cpu_it_exits_nonzero_with_no_result_line():
    proc = run(harness.ROOT)
    assert no_result(proc), proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert no_result(run(str(tmp_path)))
