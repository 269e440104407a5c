"""The command's refusals: no chip, and a checkout without the program."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT

ARGS = ["--workload", "kron17.count", "--seed", "1", "--seconds", "1", "--trace", "0"]


def run_in(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "bench.run", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = run_in(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "No module named 'repro'" in p.stderr
