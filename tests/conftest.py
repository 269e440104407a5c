"""Shared fixtures. NOTE: never set --xla_force_host_platform_device_count
here — smoke tests and benches must see the real single-CPU world; only
``repro.launch.dryrun`` (and subprocess helpers below) fake a topology.

The suite is a CPU-only tool: it runs with ``JAX_PLATFORMS=cpu`` and the
Pallas kernels in interpret mode.  ``run_multidevice`` starts children
that use JAX, which is only sound off the chip (a chip belongs to one
process), so those children are pinned to the CPU backend.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 480) -> str:
    """Run a python snippet in a subprocess with N fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr}")
    return r.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def small_graphs():
    """A family of small graphs with known-by-bruteforce triangle counts."""
    from repro.graphs import erdos_renyi, kronecker_rmat, watts_strogatz

    return {
        "er": erdos_renyi(40, 120, seed=1),
        "kron": kronecker_rmat(8, edge_factor=8, seed=2),
        "ws": watts_strogatz(60, 6, 0.2, seed=3),
        "triangle": np.array([[0, 1], [1, 0], [1, 2], [2, 1], [0, 2], [2, 0]], np.int32),
    }
