"""The open loop (``bench/loops/open.py``) on a two-tenant ``GraphService``
cell given as data alone: its schedule, a sound run, the faults of
``test_faults.py``, the control, and a service that never answers."""
import collections
import time

import numpy as np
import pytest

from bench import run as harness
from bench.control import read_controls
from bench.loops import open as open_loop
from bench.tests.small import pallas  # noqa: F401
from bench.tests.test_faults import altered, half_left_out, unchanged

KRON = {"edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
CONFIG = {"name": "two", "tenants": [
    {"name": "kron8", "graph": "kronecker", "params": dict(KRON, scale=8, seed=1503)},
    {"name": "kron7", "graph": "kronecker", "params": dict(KRON, scale=7, seed=7)}]}
TRAFFIC = {"loop": "open", "rate": 8.0, "zipf_s": 1.0,
           "mix": {"count": 0.7, "clustering": 0.3}, "drain_s": 120.0,
           "counter": {"method": "auto", "max_wedge_chunk": 16777216}}
SPEC = dict(harness.load_spec(), workloads=[
    {"name": "two.service", "config": "two", "traffic": "service", "chips": 1}])
SEED, SECONDS = 2**31 + 11, 1.0


def run_open(traffic=TRAFFIC):
    return harness.run_cell(SPEC, "two.service", SEED, SECONDS, False, config=CONFIG,
                            traffic=traffic, require_tpu=False)


@pytest.mark.parametrize("burst", [None, {"on_s": 2.0, "off_s": 6.0, "factor": 3.0}])
def test_schedule_is_drawn_from_the_seed(burst):
    traffic = dict(TRAFFIC, rate=4.0, burst=burst)
    tenants = ["kron8", "kron7"]
    one = open_loop.schedule(traffic, 7, tenants, 40.0)
    assert one == open_loop.schedule(traffic, 7, tenants, 40.0)
    other = open_loop.schedule(traffic, 2**31 + 7, tenants, 40.0)
    assert one != other
    # every seed: the same requests, in another order
    assert len(one) == len(other) == 160
    pairs = collections.Counter((g, k) for _, g, k in one)
    assert pairs == collections.Counter((g, k) for _, g, k in other)
    assert pairs == {("kron8", "count"): 75, ("kron8", "clustering"): 32,
                     ("kron7", "count"): 37, ("kron7", "clustering"): 16}
    t = np.array([a for a, _, _ in one])
    assert t[0] == 0 and (np.diff(t) >= 0).all() and t[-1] < 40.0
    if burst:
        # 3 x 4/s for 2 s in every 8 s, the rest at 4/3 per s: 24 and 8 a cycle
        on = (t % 8.0) < 2.0
        assert on.sum() == pytest.approx(0.75 * len(t), abs=8)


def test_sound_open_run_is_correct(pallas):  # noqa: F811
    result = run_open()
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == round(TRAFFIC["rate"] * SECONDS)
    assert result["compared"] == {"lcc_gap": {"value": 0.0, "limit": 0.0},
                                  "count_gap": {"value": 0, "limit": 0}}
    assert {"answer_s", "answer_p95_s", "setup_s"} <= set(result["metrics"])


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
def test_open_fault_is_caught(fault, pallas, monkeypatch):  # noqa: F811
    fault(monkeypatch)
    result = run_open()
    assert not result["correct"]
    assert result["failed"] == result["attempted"] > 0
    assert any(c["value"] > c["limit"] for c in result["compared"].values())


def test_open_control_fails():
    readings = read_controls(SPEC, "two.service", [1, 2**31 + 3], config=CONFIG,
                             traffic=TRAFFIC, require_tpu=False)
    for r in readings:
        assert r["fails"], r
        assert set(r["compared"]) == {"count_gap", "lcc_gap"}
        for c in r["compared"].values():
            assert c["value"] > c["limit"]


def test_a_service_that_never_answers_ends_after_the_drain(monkeypatch):
    from repro.serve import GraphService

    monkeypatch.setattr(GraphService, "start", lambda self: None)
    monkeypatch.setattr(open_loop.Deployment, "warm", lambda self: None)
    traffic = dict(TRAFFIC, drain_s=1.0)
    t0 = time.monotonic()
    result = run_open(traffic)
    assert time.monotonic() - t0 < 60.0
    assert not result["correct"]
    assert result["attempted"] == result["failed"] == round(TRAFFIC["rate"] * SECONDS)
