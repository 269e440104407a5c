"""Per-edge support in the benchmark: the reference against brute force
and on the configurations' stand-ins, a whole ``kron16.support`` run
sound and broken, and the scatter's metrics on a trace recorded on the
CPU (``make_cpu_support_trace.py``)."""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import graphs, reference, run as harness, trace
from bench.answers import support
from bench.graphs import kronecker
from bench.tests import make_cpu_support_trace as recorder
from bench.tests.small import TEST_SPEC, pallas, small, small_config  # noqa: F401
from bench.tests.test_faults import SEED, half_left_out
from bench.tests.test_work import karate
from repro.core.engine import PallasBackend

CELL = "kron16.support"
RECORDED = os.path.join(os.path.dirname(__file__), "data", "cpu_support.xplane.pb")


def brute(edges, n):
    """|N(u) ∩ N(v)| of every oriented edge, in (src, dst) order, by sets."""
    nbrs = [set() for _ in range(n)]
    for u, v in edges.tolist():
        nbrs[u].add(v)
    deg = [len(s) for s in nbrs]
    return np.array([len(nbrs[u] & nbrs[v]) for u in range(n) for v in sorted(nbrs[u])
                     if (deg[u], u) < (deg[v], v)], np.int64)


@pytest.mark.parametrize("scale", [5, 6])
def test_reference_matches_brute_force(scale):
    edges, n = kronecker.generate(dict(scale=scale, edge_factor=8, a=0.57, b=0.19,
                                       c=0.19, seed=scale))
    assert np.array_equal(support.reference(edges, n), brute(edges, n))


def test_karate():
    edges, n = karate()
    ref = support.reference(edges, n)
    assert np.array_equal(ref, brute(edges, n))
    assert ref.size == 78 and ref.sum() == 3 * 45 and (ref == 0).sum() > 0
    assert support.control(edges, n, 0, {}).sum() == 45


def test_blocks_change_nothing(monkeypatch):
    edges, n = kronecker.generate(dict(scale=9, edge_factor=16, a=0.57, b=0.19,
                                       c=0.19, seed=3))
    whole, ctl = support.reference(edges, n), support.control(edges, n, 0, {})
    monkeypatch.setattr(support, "BLOCK_ROWS", 37)
    assert np.array_equal(support.reference(edges, n), whole)
    assert np.array_equal(support.control(edges, n, 0, {}), ctl)


@pytest.mark.parametrize("config", [c["name"] for c in TEST_SPEC["configs"]])
def test_support_sums_to_three_counts_on_the_stand_ins(config):
    body = harness.load_json(os.path.join(harness.ROOT, harness.by_name(
        TEST_SPEC["configs"], config)["file"]))
    edges, n = graphs.generate(small(body))
    ref = support.reference(edges, n)
    assert ref.size == reference.oriented(edges, n).nnz
    assert ref.sum() == 3 * reference.triangle_count(edges, n) > 0


def run_small():
    return harness.run_cell(TEST_SPEC, CELL, SEED, 0.0, False,
                            config=small_config(CELL), require_tpu=False)


def test_sound_run_is_correct(pallas):  # noqa: F811
    result = run_small()
    assert result["correct"] and result["failed"] == 0
    assert result["compared"] == {"support_gap": {"value": 0, "limit": 0}}
    record = harness.load_json(os.path.join(harness.CACHE, "runs", f"{CELL}.{SEED}.0.json"))
    assert {a["kind"] for a in record["answers"]} == {"support"}


def altered(monkeypatch):
    intersect = PallasBackend.intersect_support

    def plus_one(self, a, b):
        count, arm, closure = intersect(self, a, b)
        return count + 1, arm, closure

    monkeypatch.setattr(PallasBackend, "intersect_support", plus_one)


def unchanged(monkeypatch):
    monkeypatch.setattr(PallasBackend, "support_chunk",
                        lambda self, adj, chunk, m_out: jnp.zeros((m_out,), jnp.int32))


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
def test_fault_is_caught(fault, pallas, monkeypatch):  # noqa: F811
    fault(monkeypatch)
    result = run_small()
    assert not result["correct"]
    assert result["failed"] == result["attempted"] > 0
    assert result["compared"]["support_gap"]["value"] > 0


def recorded_summary(answers: str | None = None):
    """The recorded window reduced as ``bench/trace.py`` reduces a chip's:
    each operation, an event that XLA's CPU runtime closes with an
    ``end: <op>`` marker, named ``jit_<function>/<op>`` after the jitted
    function that ran it; ``answers`` keeps only the operations inside
    the ``bench.answer.<answers>`` annotations."""
    data = jax.profiler.ProfileData.from_file(RECORDED)
    (line,) = [ln for p in data.planes for ln in p.lines
               if any(ev.name == trace.WINDOW for ev in ln.events)]
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
    op_names = {name[len("end: "):] for name, _, _ in evs if name.startswith("end: ")}
    calls = [(name[len("PjitFunction("):-1], s, e) for name, s, e in evs
             if name.startswith("PjitFunction(")]
    keep = [(s, e) for name, s, e in evs
            if answers is None or name == f"bench.answer.{answers}"]
    ops = []
    for name, s, e in evs:
        if name in op_names and any(a <= s < b for a, b in keep):
            (fn,) = {f for f, a, b in calls if a <= s and e <= b}
            ops.append((trace._op_name(f"jit_{fn}", name), s, e))
    host = [h for h in evs if h[0].startswith(trace.PREFIX)]
    return trace.reduce(host, {"recorded": ops})


def read(metric, summary, answers):
    csr = recorder.graph()
    deg = np.asarray(csr.out_degree, np.int64)
    needed = 4 * int((deg[np.asarray(csr.src)] + deg[np.asarray(csr.col)]).sum())
    run = harness.Run(setup_s=0.0, window_s=summary.window_s, latencies=[0.1] * answers,
                      plan_s=[], device_kind="TPU v5 lite",
                      work={"intersection_bytes": needed}, trace=summary)
    return importlib.import_module(f"bench.metrics.{metric}").read(run), run


def test_scatter_metrics_find_the_support_scatter():
    s = recorded_summary()
    scatter = {n: t for n, t in s.op_s.items() if n.startswith("jit_support_scatter/")}
    assert scatter and all(t > 0 for t in scatter.values())
    assert any("scatter" in n.split("/")[1] for n in scatter)
    assert {n.split("/")[0] for n in s.op_s} >= {"jit_support_scatter",
                                                 "jit_gather_panels_arrays"}
    share, _ = read("scatter_share", s, recorder.SUPPORT_ANSWERS)
    assert share == pytest.approx(100 * sum(scatter.values()) / s.busy_s, rel=1e-12)
    assert 0 < share < 100
    roofline, run = read("scatter_roofline_share", s, recorder.SUPPORT_ANSWERS)
    least_s = run.total("intersection_bytes") / 819e9
    assert roofline == pytest.approx(100 * least_s / sum(scatter.values()), rel=1e-12)
    assert 0 < roofline <= 100


def test_a_count_runs_no_support_scatter():
    s = recorded_summary("count")
    assert s.busy_s > 0 and not any("support_scatter" in n for n in s.op_s)
    for metric in ("scatter_share", "scatter_roofline_share"):
        assert read(metric, s, 1)[0] is None
    untraced = harness.Run(0.0, 1.0, [0.1], [], "TPU v5 lite", {"intersection_bytes": 4})
    assert importlib.import_module("bench.metrics.scatter_share").read(untraced) is None
