"""Small stand-ins for the configurations, and the cells the self-tests
run on the CPU: every cell of ``BENCHMARK.json`` and every traffic mix
kept for a later cell."""
import pytest

from bench import run as harness

SMALL = {
    "kron17": {"graph": "kronecker",
               "params": {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
                          "seed": 1503}},
    "rgg20": {"graph": "rgg", "params": {"n_log2": 11, "radius_factor": 0.55, "seed": 0}},
}
KEPT = [{"name": "kron17.lcc", "config": "kron17", "traffic": "lcc", "chips": 1}]
SPEC = harness.load_spec()
TEST_SPEC = dict(SPEC, workloads=SPEC["workloads"] + [
    w for w in KEPT if w["name"] not in {c["name"] for c in SPEC["workloads"]}])
WORKLOADS = [w["name"] for w in TEST_SPEC["workloads"]]


def small_config(workload: str) -> dict:
    return SMALL[harness.by_name(TEST_SPEC["workloads"], workload)["config"]]


@pytest.fixture
def pallas(monkeypatch):
    """``method="auto"`` resolves to the Pallas backend, as on the chip
    (interpret mode on the CPU)."""
    from repro.core import engine

    monkeypatch.setattr(engine, "choose_method", lambda **_: "pallas")
