"""The cells the self-tests run on the CPU, every cell of
``BENCHMARK.json`` and every traffic mix kept for a later cell, each on
its configuration's small stand-in: the ``small`` block of the
configuration's file, which takes the place of the keys it names."""
import os

import pytest

from bench import run as harness

KEPT = [{"name": "kron17.lcc", "config": "kron17", "traffic": "lcc", "chips": 1}]
SPEC = harness.load_spec()
TEST_SPEC = dict(SPEC, workloads=SPEC["workloads"] + [
    w for w in KEPT if w["name"] not in {c["name"] for c in SPEC["workloads"]}])
WORKLOADS = [w["name"] for w in TEST_SPEC["workloads"]]


def small(config: dict) -> dict:
    """The configuration with its small stand-in in place."""
    out = {k: v for k, v in config.items() if k != "small"}
    out.update(config["small"])
    return out


def small_config(workload: str) -> dict:
    name = harness.by_name(TEST_SPEC["workloads"], workload)["config"]
    path = harness.by_name(TEST_SPEC["configs"], name)["file"]
    return small(harness.load_json(os.path.join(harness.ROOT, path)))


@pytest.fixture
def pallas(monkeypatch):
    """``method="auto"`` resolves to the Pallas backend, as on the chip
    (interpret mode on the CPU)."""
    from repro.core import engine

    monkeypatch.setattr(engine, "choose_method", lambda **_: "pallas")
