"""TriangleCounter engine: schedule unification + memory-bounded chunking.

The acceptance contract: every schedule agrees with the NumPy oracle on
the paper's graph families, and chunked counting (any `max_wedge_chunk`)
is bit-identical to the unchunked path while the materialized wedge
buffer never exceeds the budget.
"""
import gc

import numpy as np
import pytest

from repro import obs
from repro.core import (
    TriangleCounter,
    accumulate_partials,
    choose_method,
    count_triangles,
    count_triangles_numpy,
    plan_edge_chunks,
    prepare_oriented,
    transitivity,
)
from repro.core.engine import METHODS
from repro.graphs import barabasi_albert, kronecker_rmat, watts_strogatz


@pytest.fixture(scope="module")
def family_graphs():
    """The acceptance-criteria graphs: kron10 / BA / WS."""
    return {
        "kron10": kronecker_rmat(10, seed=0),
        "barabasi_albert": barabasi_albert(2_000, 6, seed=0),
        "watts_strogatz": watts_strogatz(3_000, 10, 0.1, seed=0),
    }


@pytest.fixture(scope="module")
def family_oracle(family_graphs):
    return {name: count_triangles_numpy(e) for name, e in family_graphs.items()}


# ---------------------------------------------------------------------------
# schedule unification
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["wedge_bsearch", "panel", "pallas", "auto"])
def test_all_methods_match_numpy_oracle(family_graphs, family_oracle, method):
    for name, e in family_graphs.items():
        tc = TriangleCounter(method=method)
        assert tc.count(e) == family_oracle[name], (name, method)
        assert tc.last_stats is not None
        assert tc.last_stats.method in METHODS[1:]  # resolved, never "auto"


@pytest.mark.slow
def test_distributed_method_matches_oracle_multidevice(family_oracle):
    from conftest import run_multidevice

    out = run_multidevice("""
import jax
mesh = jax.make_mesh((2, 4), ("data", "model"))
from repro.core import TriangleCounter, count_triangles_numpy
from repro.graphs import kronecker_rmat, barabasi_albert, watts_strogatz
graphs = {
    "kron10": kronecker_rmat(10, seed=0),
    "barabasi_albert": barabasi_albert(2_000, 6, seed=0),
    "watts_strogatz": watts_strogatz(3_000, 10, 0.1, seed=0),
}
for name, e in graphs.items():
    expect = count_triangles_numpy(e)
    tc = TriangleCounter(method="distributed", mesh=mesh)
    assert tc.count(e) == expect, (name, tc.count(e), expect)
    # chunking composes with the striping: force several column chunks
    total = tc.last_stats.total_wedges
    tcc = TriangleCounter(method="distributed", mesh=mesh,
                          max_wedge_chunk=max(total // 64, 1))
    assert tcc.count(e) == expect, name
    assert tcc.last_stats.n_chunks >= 4, (name, tcc.last_stats)
print("OK")
""")
    assert "OK" in out


def test_distributed_single_device_mesh(family_graphs, family_oracle):
    """method="distributed" on the 1-device default mesh is still exact."""
    import jax

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    e = family_graphs["kron10"]
    tc = TriangleCounter(method="distributed", mesh=mesh)
    assert tc.count(e) == family_oracle["kron10"]


# ---------------------------------------------------------------------------
# memory-bounded chunking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("divisor", [4, 16, 64])
def test_chunked_equals_unchunked_all_generators(family_graphs, family_oracle, divisor):
    for name, e in family_graphs.items():
        base = TriangleCounter(method="wedge_bsearch")
        expect = base.count(e)
        assert expect == family_oracle[name]
        total = base.last_stats.total_wedges
        budget = max(total // divisor, 1)
        tc = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=budget)
        assert tc.count(e) == expect, (name, divisor)
        st = tc.last_stats
        assert st.n_chunks >= min(divisor, 2), (name, st)
        # budget respected: these budgets all exceed the forward-bound
        # max fan-out (≤ √(2m)), so the peak buffer must obey them exactly
        assert st.peak_wedge_buffer <= budget, (name, st)


def test_budget_forces_four_chunks_and_stays_bounded(family_graphs):
    e = family_graphs["kron10"]
    base = TriangleCounter(method="wedge_bsearch")
    expect = base.count(e)
    total = base.last_stats.total_wedges
    budget = total // 5
    tc = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=budget)
    assert tc.count(e) == expect
    st = tc.last_stats
    assert st.n_chunks >= 4
    assert st.peak_wedge_buffer <= budget


def test_budget_below_single_edge_fanout(family_graphs, family_oracle):
    """A budget of 1 slot cannot split an adjacency list: the engine bumps
    the buffer to the max fan-out and still counts exactly."""
    for name, e in family_graphs.items():
        tc = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=1)
        assert tc.count(e) == family_oracle[name], name
        st = tc.last_stats
        assert st.n_chunks >= 4
        # the effective buffer is bumped to (exactly) the largest
        # single-edge fan-out — far below the full wedge total
        assert st.peak_wedge_buffer < st.total_wedges
        assert st.peak_wedge_buffer <= int(np.sqrt(e.shape[0])) + 1


def test_panel_and_pallas_chunked(family_graphs, family_oracle):
    e = family_graphs["kron10"]
    for method in ["panel", "pallas"]:
        un = TriangleCounter(method=method)
        assert un.count(e) == family_oracle["kron10"]
        ck = TriangleCounter(method=method, max_wedge_chunk=512)
        assert ck.count(e) == family_oracle["kron10"], method
        assert ck.last_stats.n_chunks > un.last_stats.n_chunks, method
        # every panel gather stays within ~budget elements (one bucket row
        # may exceed it only when a single width-`w` row does)
        assert ck.last_stats.peak_wedge_buffer <= max(512, max(ck.widths))


def test_facade_kwarg_routes_chunking(family_graphs, family_oracle):
    e = family_graphs["kron10"]
    assert count_triangles(e, max_wedge_chunk=333) == family_oracle["kron10"]


def test_plan_edge_chunks_invariants():
    rng = np.random.default_rng(0)
    reps = rng.integers(0, 50, size=500)
    for budget in [None, 10_000, 1_000, 120, 49, 1]:
        bounds, eff = plan_edge_chunks(reps, budget)
        # exact cover, in order, no overlap
        assert bounds[0][0] == 0 and bounds[-1][1] == len(reps)
        for (a0, a1), (b0, b1) in zip(bounds, bounds[1:]):
            assert a1 == b0
        # every chunk within the effective budget
        for s, t in bounds:
            assert reps[s:t].sum() <= eff
        if budget is not None:
            assert eff >= min(budget, int(reps.max()))


# ---------------------------------------------------------------------------
# uint64 accumulation
# ---------------------------------------------------------------------------


def test_uint64_accumulation_regression():
    """Partial sums near int32 max must not wrap when combined on host —
    the paper's Table I counts (3.8B) exceed 2³¹."""
    near_max = np.int32(2**31 - 1)
    partials = [near_max] * 4
    expect = 4 * (2**31 - 1)  # 8589934588 > 2**32
    assert accumulate_partials(partials) == expect
    # mixed arrays and scalars, including empty
    parts = [np.array([near_max, near_max], np.int32), np.int32(7), np.array([], np.int32)]
    assert accumulate_partials(parts) == 2 * (2**31 - 1) + 7


def test_engine_fold_of_a_total_past_int32(family_graphs):
    """The engine's own fold of device partials, end to end, on a total
    past 2³¹: every chunk reports three near-int32-max partials."""
    from repro.core.engine import WedgeBackend, register_backend, _BACKEND_FACTORIES

    near_max = 2**31 - 1

    class Saturating(WedgeBackend):
        name = "saturating"

        def count_chunk(self, adj, chunk):
            return np.full((3,), near_max, np.int32)

    register_backend("saturating", lambda **_: Saturating())
    try:
        tc = TriangleCounter(method="saturating", max_wedge_chunk=64)
        total = tc.count(family_graphs["kron10"])
        n = tc.last_stats.n_chunks
        assert n > 1 and total == 3 * n * near_max > 2**32
    finally:
        del _BACKEND_FACTORIES["saturating"]


def test_accumulation_matches_over_many_chunks(family_graphs, family_oracle):
    """Many tiny chunks exercise the host accumulation path end to end."""
    e = family_graphs["watts_strogatz"]
    tc = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=64)
    assert tc.count(e) == family_oracle["watts_strogatz"]
    assert tc.last_stats.n_chunks > 100


# ---------------------------------------------------------------------------
# per-node / clustering / auto dispatch
# ---------------------------------------------------------------------------


def test_per_node_and_clustering_chunked(family_graphs, family_oracle):
    e = family_graphs["kron10"]
    tc = TriangleCounter(max_wedge_chunk=1_000)
    pn = tc.per_node(e)
    assert int(pn.sum()) // 3 == family_oracle["kron10"]
    cc = tc.clustering(e)
    assert cc.shape == pn.shape
    assert (cc >= 0).all() and (cc <= 1).all()
    assert abs(tc.transitivity(e) - transitivity(e)) < 1e-12


def test_auto_dispatch_stats():
    assert choose_method(max_out_degree=10, mean_out_degree=5.0, backend="cpu") == "panel"
    assert (
        choose_method(max_out_degree=4000, mean_out_degree=8.0, backend="cpu")
        == "wedge_bsearch"
    )
    assert (
        choose_method(max_out_degree=100, mean_out_degree=50.0, backend="tpu")
        == "pallas"
    )


def test_per_node_executes_configured_backend(family_graphs):
    """per_node now runs the configured backend natively — the stats must
    prove the non-wedge backend actually executed (no silent fallback)."""
    e = family_graphs["kron10"]
    for configured in ["panel", "pallas"]:
        tc = TriangleCounter(method=configured)
        tc.per_node(e)
        assert tc.last_stats.method == configured
        assert tc.last_stats.resolved_method == configured
        assert tc.last_stats.fallback_reason is None
    # auto dispatch: resolved is whatever choose_method picked, never "auto"
    tc = TriangleCounter(method="auto")
    tc.per_node(e)
    assert tc.last_stats.resolved_method in METHODS[1:]
    # count paths execute what they resolve
    tc2 = TriangleCounter(method="panel")
    tc2.count(e)
    assert tc2.last_stats.method == tc2.last_stats.resolved_method == "panel"


def test_per_node_and_support_bit_identical_across_backends(family_graphs):
    """The acceptance criterion: per-node and per-edge-support outputs are
    bit-identical across wedge/panel/pallas at ≥2 budgets, with
    EngineStats.method proving the non-wedge backend executed."""
    e = family_graphs["kron10"]
    base = TriangleCounter(method="wedge_bsearch")
    pn0 = base.per_node(e)
    sup0 = base.edge_support(e)
    assert int(sup0.sum()) == 3 * base.count(e)
    total = base.last_stats.total_wedges
    for method in ["panel", "pallas"]:
        for budget in [max(total // 4, 1), max(total // 16, 1)]:
            tc = TriangleCounter(method=method, max_wedge_chunk=budget)
            np.testing.assert_array_equal(tc.per_node(e), pn0)
            assert tc.last_stats.method == method
            assert tc.last_stats.n_chunks > 1
            np.testing.assert_array_equal(tc.edge_support(e), sup0)
            assert tc.last_stats.method == method


def test_distributed_runs_every_workload(family_graphs):
    """distributed now carries per_node/support kernels: on a 1×1 mesh every
    workload executes the striped schedule bit-identically — no fallback."""
    import jax

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    e = family_graphs["kron10"]
    base = TriangleCounter(method="wedge_bsearch")
    expect_count = base.count(e)
    pn0 = base.per_node(e)
    sup0 = base.edge_support(e)
    tc = TriangleCounter(method="distributed", mesh=mesh)
    assert tc.count(e) == expect_count
    assert tc.last_stats.method == "distributed"
    assert tc.last_stats.fallback_reason is None
    np.testing.assert_array_equal(tc.per_node(e), pn0)
    st = tc.last_stats
    assert st.method == "distributed"
    assert st.resolved_method == "distributed"
    assert st.fallback_reason is None
    assert st.n_stripes == 1
    np.testing.assert_array_equal(tc.edge_support(e), sup0)
    assert tc.last_stats.method == "distributed"
    assert tc.last_stats.fallback_reason is None


def test_capability_fallback_is_loud_and_not_sticky(family_graphs):
    """A backend lacking a kernel falls back loudly — and the recorded
    fallback_reason must not leak into the next (clean) call on the same
    reused counter."""
    import warnings

    from repro.core.engine import (
        WedgeBackend,
        register_backend,
        _BACKEND_FACTORIES,
        _warned_fallbacks,
    )

    class CountOnly(WedgeBackend):
        name = "count_only"
        capabilities = frozenset({"count"})

    e = family_graphs["kron10"]
    base = TriangleCounter(method="wedge_bsearch")
    pn0 = base.per_node(e)
    expect_count = base.count(e)
    register_backend("count_only", lambda **_: CountOnly())
    try:
        _warned_fallbacks.clear()
        tc = TriangleCounter(method="count_only")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            pn = tc.per_node(e)
        assert [w for w in caught if issubclass(w.category, RuntimeWarning)]
        np.testing.assert_array_equal(pn, pn0)
        st = tc.last_stats
        assert st.method == "wedge_bsearch"
        assert st.resolved_method == "count_only"
        assert st.fallback_reason and "per_node" in st.fallback_reason
        # the warning is one-time per (method, kind) pair
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            tc.per_node(e)
        assert not [w for w in caught if issubclass(w.category, RuntimeWarning)]
        # regression: stats are per-invocation — a subsequent clean call on
        # the same counter must not report the stale fallback_reason
        assert tc.count(e) == expect_count
        assert tc.last_stats.method == "count_only"
        assert tc.last_stats.fallback_reason is None
    finally:
        del _BACKEND_FACTORIES["count_only"]


def test_backend_registry_roundtrip():
    """make_backend resolves registered names; unknown names fail loudly;
    custom registrations are honored."""
    from repro.core.engine import (
        CAPABILITIES,
        WedgeBackend,
        make_backend,
        register_backend,
        resolve_backend,
        _BACKEND_FACTORIES,
    )

    for name, expected in [
        ("wedge_bsearch", "wedge_bsearch"),
        ("panel", "panel"),
        ("pallas", "pallas"),
        ("distributed", "distributed"),
    ]:
        assert make_backend(name).name == expected
    with pytest.raises(ValueError):
        make_backend("nope")
    with pytest.raises(ValueError):
        resolve_backend("wedge_bsearch", "frobnicate")
    assert set(CAPABILITIES) == {"count", "per_node", "support"}
    register_backend("test_custom", lambda **_: WedgeBackend())
    try:
        assert make_backend("test_custom").name == "wedge_bsearch"
    finally:
        del _BACKEND_FACTORIES["test_custom"]


def test_peak_buffer_is_true_chunk_load(family_graphs):
    """peak_wedge_buffer reports the largest buffer actually materialized
    (the max chunk load), not the requested budget."""
    e = family_graphs["kron10"]
    base = TriangleCounter(method="wedge_bsearch")
    expect = base.count(e)
    total = base.last_stats.total_wedges
    # unchunked: the whole workload is the buffer
    assert base.last_stats.peak_wedge_buffer == total
    budget = total // 3
    tc = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=budget)
    assert tc.count(e) == expect
    st = tc.last_stats
    # the greedy plan rarely fills the budget exactly: the true peak is
    # what the kernels saw, and it must match the plan's chunk loads
    import jax.numpy as jnp

    from repro.core import preprocess

    csr = preprocess(jnp.asarray(e), n_nodes=int(e.max()) + 1)
    out_deg = np.asarray(csr.out_degree)
    reps = out_deg[np.asarray(csr.src)].astype(np.int64)
    bounds, _ = plan_edge_chunks(reps, budget)
    true_peak = max(int(reps[s:t].sum()) for s, t in bounds)
    assert st.peak_wedge_buffer == true_peak
    assert st.peak_wedge_buffer <= budget


def test_engine_rejects_bad_args():
    with pytest.raises(ValueError):
        TriangleCounter(method="nope")
    with pytest.raises(ValueError):
        TriangleCounter(method="distributed")  # no mesh
    with pytest.raises(ValueError):
        TriangleCounter(max_wedge_chunk=0)


def test_empty_graph():
    tc = TriangleCounter()
    assert tc.count(np.zeros((0, 2), np.int32)) == 0
    assert tc.per_node(np.zeros((0, 2), np.int32), n_nodes=5).shape == (5,)


# ---------------------------------------------------------------------------
# a resident graph's panel plan, built once and kept
# ---------------------------------------------------------------------------


def _plan_tally():
    counters = obs.metrics_snapshot()["counters"]
    return counters.get("engine.plans_built", 0), counters.get("engine.plans_reused", 0)


def _dense_oracles(e):
    """Count (core/baseline.py), per-vertex triangles (diag A³ / 2) and the
    support of every oriented edge (A² at it) of a small graph."""
    n = int(e.max()) + 1
    a = np.zeros((n, n), np.int64)
    a[e[:, 0], e[:, 1]] = 1
    a2 = a @ a
    return count_triangles_numpy(e), np.diag(a2 @ a) // 2, a2


@pytest.fixture(scope="module")
def resident_kron():
    e = kronecker_rmat(8, seed=0)
    return e, _dense_oracles(e)


@pytest.mark.parametrize("kind", ["count", "per_node", "edge_support"])
@pytest.mark.parametrize("method", ["panel", "pallas"])
def test_resident_graph_is_planned_once(method, kind, resident_kron):
    e, (count, per_node, a2) = resident_kron
    csr = prepare_oriented(e)
    want = {"count": count, "per_node": per_node,
            "edge_support": a2[np.asarray(csr.src), np.asarray(csr.col)]}[kind]
    tc = TriangleCounter(method=method, max_wedge_chunk=1 << 10)
    built, reused = _plan_tally()
    with obs.tracing() as t:
        answers = [getattr(tc, kind)(csr) for _ in range(3)]
    for got in answers:
        assert np.array_equal(got, want)
        assert np.array_equal(got, answers[0])
    assert tc.last_stats.n_chunks > 1
    assert _plan_tally() == (built + 1, reused + 2)
    plans = [ev["args"] for ev in t.events if ev["name"] == "engine.plan"]
    assert [p["reused"] for p in plans] == [0, 1, 1]
    # the chunk index arrays went to the device with the kept plan
    h2d = [ev["args"]["h2d_bytes"] for ev in t.events if ev["name"] == "engine.dispatch"]
    assert h2d and not any(h2d)


def test_kept_plan_dies_with_its_graph(resident_kron):
    e, (count, _, _) = resident_kron
    perm = np.random.default_rng(0).permutation(int(e.max()) + 1)
    e_b = perm[e]
    _, per_node_b, _ = _dense_oracles(e_b)
    tc = TriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    built, reused = _plan_tally()
    csr_a = prepare_oriented(e)
    assert tc.count(csr_a) == count
    shapes = [a.shape for a in csr_a]
    del csr_a
    gc.collect()
    assert not tc._plans._entries
    csr_b = prepare_oriented(e_b)
    assert [a.shape for a in csr_b] == shapes
    assert not np.array_equal(np.asarray(csr_b.col), np.asarray(prepare_oriented(e).col))
    assert tc.count(csr_b) == count_triangles_numpy(e_b)
    assert np.array_equal(tc.per_node(csr_b), per_node_b)
    assert _plan_tally() == (built + 2, reused + 1)


def test_host_workloads_never_reuse_a_plan(resident_kron):
    import jax

    from repro.core import IncrementalTriangleCounter
    from repro.core.engine import PanelBackend, make_workload, run_workload

    e, (count, _, _) = resident_kron
    csr = prepare_oriented(e)
    _, reused = _plan_tally()
    host = make_workload(*(np.asarray(a) for a in (csr.row_offsets, csr.col,
                                                    csr.out_degree, csr.src, csr.col)))
    for _ in range(2):
        assert run_workload(PanelBackend(), "count", host, budget=1 << 10)[0] == count
    # a NumPy edge array is oriented anew on every call
    tc = TriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    assert tc.count(e) == tc.count(e) == count
    # the wedge and striped schedules plan every answer
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for other in (TriangleCounter(method="wedge_bsearch", max_wedge_chunk=1 << 10),
                  TriangleCounter(method="distributed", mesh=mesh)):
        assert other.count(csr) == other.count(csr) == count
    itc = IncrementalTriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    for batch in np.array_split(e[e[:, 0] < e[:, 1]], 2):
        itc.insert(batch)
    assert itc.count == count
    assert itc.last_update_stats.probe_method == "panel"
    assert _plan_tally()[1] == reused


def test_kept_plans_follow_the_budget(resident_kron):
    from repro.core.engine import PanelBackend, workload_from_csr

    e, (count, _, _) = resident_kron
    csr = prepare_oriented(e)
    for budget in (1 << 10, 1 << 12):
        want = PanelBackend().plan(workload_from_csr(csr), budget).n_chunks
        tc = TriangleCounter(method="panel", max_wedge_chunk=budget)
        for _ in range(2):
            assert tc.count(csr) == count
            assert tc.last_stats.n_chunks == want
    assert PanelBackend().plan(workload_from_csr(csr), 1 << 10).n_chunks > want
