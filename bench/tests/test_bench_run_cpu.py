"""``bench/run.py`` refuses to measure without a chip or without the program:
it exits non-zero and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

from benchlib import spec


def run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "int8dev-poisson",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(proc):
    lines = proc.stdout.strip().splitlines()
    assert not any(line.startswith("{") for line in lines), proc.stdout
    assert "metrics" not in proc.stdout


def test_cpu_device_exits_nonzero_without_metrics():
    proc = run_bench(spec.ROOT)
    assert proc.returncode != 0
    assert "NoChip" in proc.stderr
    no_result(proc)


def test_bench_files_alone_exit_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_bench(tmp_path)
    assert proc.returncode != 0
    assert "ProgramMissing" in proc.stderr
    no_result(proc)
    json.loads((tmp_path / "BENCHMARK.json").read_text())
