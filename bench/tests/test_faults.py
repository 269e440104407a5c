"""A whole run, with the chip check skipped and the timed path broken
underneath, comes out not correct; unbroken, it comes out correct.

The faults a one-chip answer can have: the answer altered where it is
produced (the kernel's per-edge counts), half of every chunk left out,
and the work skipped so that the answer's accumulator never moves.  No
cell exchanges data between chips, so that fault has no test.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import graphs, run as harness
from bench.tests.small import TEST_SPEC, WORKLOADS, pallas, small_config  # noqa: F401
from repro.core.engine import PallasBackend, PanelChunk


def altered(monkeypatch):
    count, per_node = PallasBackend.intersect_count, PallasBackend.intersect_per_node
    monkeypatch.setattr(PallasBackend, "intersect_count",
                        lambda self, a, b: count(self, a, b) + 1)

    def per_node_plus(self, a, b):
        c, arm = per_node(self, a, b)
        return c + 1, arm

    monkeypatch.setattr(PallasBackend, "intersect_per_node", per_node_plus)


def half_left_out(monkeypatch):
    plan = PallasBackend.plan

    def halve(chunk):
        u, v = np.array(chunk.u), np.array(chunk.v)
        u[len(u) // 2:] = -1
        v[len(v) // 2:] = -1
        return PanelChunk(chunk.edge_idx, u, v, chunk.width)

    def half_plan(self, work, budget, **kw):
        p = plan(self, work, budget, **kw)
        return p._replace(chunks=iter([halve(c) for c in p.chunks]))

    monkeypatch.setattr(PallasBackend, "plan", half_plan)


def unchanged(monkeypatch):
    monkeypatch.setattr(PallasBackend, "count_chunk",
                        lambda self, adj, chunk: jnp.zeros((1,), jnp.int32))
    monkeypatch.setattr(PallasBackend, "per_node_chunk",
                        lambda self, adj, chunk, n_out: jnp.zeros((n_out,), jnp.int32))


SEED = 2**31 + 9


def run_small(workload):
    return harness.run_cell(TEST_SPEC, workload, SEED, 0.0, False,
                            config=small_config(workload), require_tpu=False)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload, pallas):  # noqa: F811
    result = run_small(workload)
    assert result["correct"] and result["failed"] == 0
    assert list(result)[-1] == "compared"
    kind = harness.load_json(os.path.join(
        harness.ROOT, "bench", "traffic",
        f"{harness.by_name(TEST_SPEC['workloads'], workload)['traffic']}.json"))["answer"]
    gap = {"count": "count_gap", "clustering": "lcc_gap"}[kind]
    assert result["compared"] == {gap: {"value": 0, "limit": 0}}
    # the window's work is its answers times one answer's on the one graph
    config = small_config(workload)
    base, n_nodes = graphs.generate(config)
    one = harness.work_of(graphs.relabel(base, n_nodes, SEED), n_nodes)
    with open(os.path.join(harness.CACHE, "runs", f"{workload}.{SEED}.0.json")) as f:
        record = json.load(f)
    assert record["work"] == {config["name"]: one}
    assert {a["graph"] for a in record["answers"]} == {config["name"]}
    assert {a["kind"] for a in record["answers"]} == {kind}
    for key in ("intersection_bytes", "compares", "padded_compares"):
        assert record["window_work"][key] == result["attempted"] * one[key]


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_is_caught(workload, fault, pallas, monkeypatch):  # noqa: F811
    fault(monkeypatch)
    result = run_small(workload)
    assert not result["correct"]
    assert result["failed"] == result["attempted"] > 0
    assert any(c["value"] > c["limit"] for c in result["compared"].values())
