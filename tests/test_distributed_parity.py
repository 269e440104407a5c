"""§III-E distributed parity: every workload, bit-identical on a simulated mesh.

The acceptance contract of the distributed backend is not "close" — it is
**bit-identical** to the single-device wedge schedule for every workload
it claims: count, per-node incidences, per-edge support, the full truss
spectrum, and the incremental engine's delta probes.  These tests prove
it on simulated meshes of 2 / 4 / 8 CPU devices
(``--xla_force_host_platform_device_count``, via ``conftest.run_multidevice``
subprocesses — the parent process must keep its real single-device world),
at multiple ``max_wedge_chunk`` budgets, with the delta-compressed and
uncompressed support wires, and from sharded ``.tricsr`` slab views.

A hypothesis(-stub) property test fuzzes random small graphs × random
device counts, including the degenerate stripes (more devices than
edges, empty graphs, single edges) where striping logic dies first.
"""
import os
import sys

import numpy as np
import pytest

from conftest import run_multidevice

pytestmark = pytest.mark.distributed

# ---------------------------------------------------------------------------
# subprocess preamble shared by the mesh tests
# ---------------------------------------------------------------------------

_PRELUDE = """
import numpy as np
import jax
from jax.sharding import Mesh

from repro.core import TriangleCounter
from repro.graphs.generators import kronecker_rmat
from repro.graphs.io.registry import karate_edges

K = {k}
mesh = Mesh(np.array(jax.devices()[:K]), ("edges",))
"""


@pytest.mark.slow
@pytest.mark.parametrize("k", [2, 4, 8])
def test_mesh_parity_count_per_node_support(k):
    """count / per_node / edge_support bit-identical to wedge on karate and
    kron-10, at two budgets, with EngineStats attesting the striped run."""
    out = run_multidevice(_PRELUDE.format(k=k) + """
graphs = {"karate": karate_edges(), "kron10": kronecker_rmat(10, seed=0)}
for name, e in graphs.items():
    wedge = TriangleCounter(method="wedge_bsearch")
    for budget in (None, 2048):
        dist = TriangleCounter(method="distributed", mesh=mesh,
                               max_wedge_chunk=budget)
        ref = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=budget)
        assert dist.count(e) == ref.count(e), (name, budget)
        st = dist.last_stats
        assert st.method == "distributed" and st.fallback_reason is None
        assert st.n_stripes == K, st
        assert np.array_equal(dist.per_node(e), ref.per_node(e)), (name, budget)
        assert dist.last_stats.method == "distributed"
        assert np.array_equal(dist.edge_support(e), ref.edge_support(e))
        st = dist.last_stats
        assert st.method == "distributed" and st.fallback_reason is None
        assert st.n_stripes == K and st.stripe_skew is not None and st.stripe_skew >= 1.0
print("PARITY_OK")
""")
    assert "PARITY_OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("k", [2, 8])
def test_mesh_parity_truss_and_incremental(k):
    """Truss spectrum and incremental insert/delete deltas bit-identical,
    with the probes attesting probe_method == "distributed"."""
    out = run_multidevice(_PRELUDE.format(k=k) + """
from repro.analytics.truss import k_truss_decomposition
from repro.core.incremental import IncrementalTriangleCounter

graphs = {"karate": karate_edges(),
          "kron9": kronecker_rmat(9, edge_factor=8, seed=2)}
for name, e in graphs.items():
    for budget in (None, 1024):
        td = k_truss_decomposition(e, max_wedge_chunk=budget,
                                   method="distributed", mesh=mesh)
        tw = k_truss_decomposition(e, max_wedge_chunk=budget,
                                   method="wedge_bsearch")
        assert td.method == "distributed", td.method
        assert np.array_equal(td.trussness, tw.trussness), (name, budget)
        assert td.spectrum() == tw.spectrum() and td.max_k == tw.max_k

        canon = np.asarray(e, np.int64).reshape(-1, 2)
        half = canon[: canon.shape[0] // 2]
        rest = canon[canon.shape[0] // 2:]
        inc_d = IncrementalTriangleCounter(half, max_wedge_chunk=budget,
                                           method="distributed", mesh=mesh)
        inc_w = IncrementalTriangleCounter(half, max_wedge_chunk=budget,
                                           method="wedge_bsearch")
        assert inc_d.probe_method == "distributed"
        assert inc_d.insert(rest) == inc_w.insert(rest), (name, budget)
        assert inc_d.last_update_stats.probe_method == "distributed"
        assert inc_d.count == inc_w.count
        assert np.array_equal(inc_d.per_node(), inc_w.per_node())
        assert inc_d.delete(rest[:40]) == inc_w.delete(rest[:40])
        assert inc_d.count == inc_w.count
        assert np.array_equal(inc_d.per_node(), inc_w.per_node())
print("TRUSS_INC_OK")
""")
    assert "TRUSS_INC_OK" in out


@pytest.mark.slow
def test_mesh_parity_kron12_8way():
    """The big graph: kron-12 count/per-node/support on the full 8-mesh."""
    out = run_multidevice(_PRELUDE.format(k=8) + """
e = kronecker_rmat(12, seed=0)
dist = TriangleCounter(method="distributed", mesh=mesh,
                       max_wedge_chunk=1 << 20)
ref = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=1 << 20)
assert dist.count(e) == ref.count(e)
assert dist.last_stats.method == "distributed"
assert dist.last_stats.n_stripes == 8
assert np.array_equal(dist.per_node(e), ref.per_node(e))
assert np.array_equal(dist.edge_support(e), ref.edge_support(e))
print("KRON12_OK")
""")
    assert "KRON12_OK" in out


@pytest.mark.slow
def test_support_compression_bit_identity():
    """The delta-compressed (uint16-wire) support all-gather and the plain
    int32 wire produce the same bits, and both match wedge."""
    out = run_multidevice(_PRELUDE.format(k=8) + """
from repro.core.engine import (DistributedBackend, make_workload,
                               prepare_oriented, run_workload)

e = kronecker_rmat(10, seed=0)
csr = prepare_oriented(e, None)
work = make_workload(csr.row_offsets, csr.col, csr.out_degree, csr.src, csr.col)
ref = TriangleCounter(method="wedge_bsearch").edge_support(e)
for budget in (None, 4096):
    for compress in (True, False):
        bk = DistributedBackend(mesh, compress=compress)
        sup, plan = run_workload(bk, "support", work, budget=budget)
        assert np.array_equal(sup, ref), (budget, compress)
print("COMPRESS_OK")
""")
    assert "COMPRESS_OK" in out


@pytest.mark.slow
def test_slab_views_feed_distributed_count():
    """Sharded .tricsr slab views orient and count on the mesh with no full
    col array ever assembled — same count as the single-device oracle."""
    out = run_multidevice(_PRELUDE.format(k=8) + """
import tempfile, os
from repro.graphs.formats import canonicalize_edges, edge_array_to_csr
from repro.graphs.io import CSRGraph, save_tricsr_stripes, load_tricsr_stripes
from repro.core.distributed import count_triangles_distributed_slabs

e = kronecker_rmat(10, seed=0)
canon = canonicalize_edges(e)
row, col = edge_array_to_csr(canon)
csr = CSRGraph(row, col, row.shape[0] - 1)
expect = TriangleCounter(method="wedge_bsearch").count(e)
with tempfile.TemporaryDirectory() as d:
    base = os.path.join(d, "g.tricsr")
    save_tricsr_stripes(base, csr, 8)
    slabs = load_tricsr_stripes(base, 8, verify=True)
    stats = {}
    got = count_triangles_distributed_slabs(slabs, mesh, stats_out=stats)
assert got == expect, (got, expect)
assert stats.get("n_chunks", 1) >= 1
print("SLAB_COUNT_OK")
""")
    assert "SLAB_COUNT_OK" in out


@pytest.mark.slow
def test_property_striped_equals_oracle_random_graphs():
    """Hypothesis(-stub) fuzz: random small graphs × random device counts
    (1–8) — striped per_node/support == the single-device oracle, including
    the degenerate stripes (devices > edges, empty stripes, one edge)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    out = run_multidevice(f"""
import sys
sys.path.insert(0, {tests_dir!r})
""" + """
import numpy as np
import jax
from jax.sharding import Mesh

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, st

from repro.core import TriangleCounter
from repro.graphs import canonicalize_edges

DEVS = jax.devices()


def random_edges(rnd, n, m):
    if m == 0:
        return np.zeros((0, 2), np.int32)
    u = np.array([rnd.randrange(n) for _ in range(m)], np.int32)
    v = np.array([rnd.randrange(n) for _ in range(m)], np.int32)
    return canonicalize_edges(np.stack([u, v], axis=1))


# no deadline: the first example of each shape pays a jit compile, and
# derandomized draws keep the example set the same on every run
@settings(max_examples=20, deadline=None, derandomize=True)
@given(st.randoms(), st.integers(2, 40), st.integers(0, 120),
       st.integers(1, 8), st.sampled_from([None, 1, 64]))
def check(rnd, n, m, k, budget):
    e = random_edges(rnd, n, m)
    mesh = Mesh(np.array(DEVS[:k]), ("edges",))
    ref = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=budget)
    dist = TriangleCounter(method="distributed", mesh=mesh,
                           max_wedge_chunk=budget)
    assert dist.count(e) == ref.count(e)
    assert np.array_equal(dist.per_node(e), ref.per_node(e))
    assert np.array_equal(dist.edge_support(e), ref.edge_support(e))
    if e.shape[0]:
        assert dist.last_stats.method == "distributed"
        assert dist.last_stats.n_stripes == k


check()

# pinned degenerate stripes: devices > edges, a single edge, empty graph
mesh8 = Mesh(np.array(DEVS), ("edges",))
for e in [np.zeros((0, 2), np.int32),
          np.array([[0, 1], [1, 0]], np.int32),
          np.array([[0, 1], [1, 2], [0, 2], [1, 0], [2, 1], [2, 0]], np.int32)]:
    ref = TriangleCounter(method="wedge_bsearch")
    dist = TriangleCounter(method="distributed", mesh=mesh8)
    assert dist.count(e) == ref.count(e)
    assert np.array_equal(dist.per_node(e), ref.per_node(e))
    assert np.array_equal(dist.edge_support(e), ref.edge_support(e))
print("PROPERTY_OK")
""", timeout=560)
    assert "PROPERTY_OK" in out


@pytest.mark.slow
def test_serve_graph_distributed_smoke():
    """serve_graph --method distributed serves karate and its final oracle
    recount agrees (exits 0, prints the distributed probe backend)."""
    import subprocess

    from conftest import SRC

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "karate.txt")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_graph",
         "--input", fixture, "--batch-size", "16",
         "--queries-per-batch", "1", "--method", "distributed"],
        capture_output=True, text=True, env=env, timeout=480,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "probe backend: distributed" in r.stdout
    assert "verify: from-scratch recount agrees" in r.stdout


# ---------------------------------------------------------------------------
# in-process pieces (single real device — no subprocess needed)
# ---------------------------------------------------------------------------


def test_single_device_mesh_parity_in_process(small_graphs):
    """A 1×1 mesh exercises the full striped path in-process: every
    workload bit-identical, stats attesting the distributed schedule."""
    import jax
    from jax.sharding import Mesh

    from repro.core import TriangleCounter

    mesh = Mesh(np.array(jax.devices()[:1]), ("edges",))
    e = small_graphs["kron"]
    for budget in (None, 256):
        dist = TriangleCounter(method="distributed", mesh=mesh,
                               max_wedge_chunk=budget)
        base = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=budget)
        assert dist.count(e) == base.count(e)
        assert dist.last_stats.method == "distributed"
        assert dist.last_stats.n_stripes == 1
        np.testing.assert_array_equal(dist.per_node(e), base.per_node(e))
        np.testing.assert_array_equal(dist.edge_support(e), base.edge_support(e))
        st = dist.last_stats
        assert st.stripe_skew == 1.0 and st.straggler_stripe is None


def test_stripe_skew_report_flags_outlier():
    """The median+MAD rule flags a grossly overloaded stripe and reports
    the max/mean skew factor."""
    from repro.distributed.straggler import stripe_skew_report

    rep = stripe_skew_report([10, 10, 10, 100])
    assert rep.n_stripes == 4
    assert rep.max_load == 100 and rep.straggler_stripe == 3
    assert rep.skew == pytest.approx(100 / 32.5)
    balanced = stripe_skew_report([50, 51, 49, 50])
    assert balanced.straggler_stripe is None
    assert balanced.skew == pytest.approx(51 / 50.0)
    empty = stripe_skew_report([])
    assert empty.straggler_stripe is None and empty.skew == 1.0


def test_stale_fallback_reason_not_reported_by_distributed(small_graphs):
    """Regression: EngineStats are per-invocation — after a capability
    fallback on one counter, a distributed call must report a clean
    fallback_reason, and a later clean call on the *same* fallen-back
    counter must too."""
    import jax
    from jax.sharding import Mesh

    from repro.core import TriangleCounter
    from repro.core.engine import (
        WedgeBackend,
        register_backend,
        _BACKEND_FACTORIES,
    )

    class CountOnly(WedgeBackend):
        name = "count_only"
        capabilities = frozenset({"count"})

    e = small_graphs["kron"]
    register_backend("count_only", lambda **_: CountOnly())
    try:
        crippled = TriangleCounter(method="count_only")
        crippled.per_node(e)  # falls back to wedge
        assert crippled.last_stats.fallback_reason is not None
        mesh = Mesh(np.array(jax.devices()[:1]), ("edges",))
        dist = TriangleCounter(method="distributed", mesh=mesh)
        dist.per_node(e)
        assert dist.last_stats.method == "distributed"
        assert dist.last_stats.fallback_reason is None
        # the crippled counter's next capable call is clean too
        crippled.count(e)
        assert crippled.last_stats.fallback_reason is None
    finally:
        del _BACKEND_FACTORIES["count_only"]
