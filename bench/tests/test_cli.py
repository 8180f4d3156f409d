"""The command refuses to run without a chip, and without the program."""
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "kdd99.ingest", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_non_zero_on_a_cpu_only_host():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
