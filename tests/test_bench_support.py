"""The program's per-edge support against the benchmark's own reference
(``bench/answers/support.py``), on small stand-ins of the benchmark's
graphs relabelled as a run relabels them, through every schedule that
answers it; and the order the answer comes in, which the reference
assumes: the edges of the forward orientation by ``(degree, id)``, in
``(src, dst)`` order.  The scale-10 Kronecker stand-in takes edge factor
32 so that some edges fall in the width-256 bucket, as most of
``kron16``'s do."""
import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import graphs  # noqa: E402
from bench import reference  # noqa: E402
from bench.answers import support  # noqa: E402

KRON = {"graph": "kronecker", "params": {"edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
                                         "seed": 1503}}
STAND_INS = {
    "kron8": dict(KRON, params=dict(KRON["params"], scale=8)),
    "kron10": dict(KRON, params=dict(KRON["params"], scale=10, edge_factor=32)),
    "rgg11": {"graph": "rgg", "params": {"n_log2": 11, "radius_factor": 0.55, "seed": 0}},
}
SEEDS = (1, 2**31 + 7, 4000000011)
METHODS = ("wedge_bsearch", "panel", "pallas")
BUDGET = 1 << 24      # the benchmark's wedge budget (bench/traffic/support.json)


@functools.cache
def relabelled(name: str, seed: int):
    """``(edges, n_nodes, csr, reference support)`` of one stand-in and seed."""
    from repro.core import prepare_oriented

    base, n = graphs.generate(STAND_INS[name])
    edges = graphs.relabel(base, n, seed)
    return edges, n, prepare_oriented(edges, n), support.reference(edges, n)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(STAND_INS))
@pytest.mark.parametrize("method", METHODS)
def test_edge_support_matches_the_reference(method, name, seed):
    from repro.core import TriangleCounter

    _, _, csr, ref = relabelled(name, seed)
    got = TriangleCounter(method=method, max_wedge_chunk=BUDGET).edge_support(csr)
    assert got.dtype == np.int64 and got.shape == ref.shape
    failed, compared = support.compare([got], ref)
    assert failed == 0 and compared == {"support_gap": (0, 0)}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(STAND_INS))
def test_answer_order_is_the_oriented_csr_order(name, seed):
    """Forward orientation by ``(degree, id)``, edges in ``(src, dst)``
    order: the program's CSR lists the reference's entries in its order."""
    edges, n, csr, _ = relabelled(name, seed)
    src, dst = np.asarray(csr.src, np.int64), np.asarray(csr.col, np.int64)
    deg = np.bincount(edges[:, 0], minlength=n)
    assert ((deg[src] < deg[dst]) | ((deg[src] == deg[dst]) & (src < dst))).all()
    assert np.array_equal(np.lexsort((dst, src)), np.arange(src.size))
    a = reference.oriented(edges, n)
    a.sort_indices()
    rows = np.repeat(np.arange(n), np.diff(a.indptr))
    assert np.array_equal(src, rows) and np.array_equal(dst, a.indices)


def test_a_stand_in_fills_the_width_256_bucket():
    from repro.core.engine import PanelBackend, workload_from_csr

    _, _, csr, _ = relabelled("kron10", SEEDS[0])
    widths = {c.width for c in PanelBackend().plan(workload_from_csr(csr), BUDGET).chunks}
    assert 256 in widths


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(STAND_INS))
def test_control_fails_the_comparison(name, seed):
    edges, n, _, ref = relabelled(name, seed)
    ctl = support.control(edges, n, seed, {})
    failed, compared = support.compare([ctl], ref)
    gap, limit = compared["support_gap"]
    assert failed == 1 and gap > limit
    # the base-edge-only billing counts each triangle once, not three times
    assert ctl.sum() * 3 == ref.sum() == 3 * reference.triangle_count(edges, n)


def test_a_wrong_shape_reads_the_widest_gap():
    ref = np.zeros(5, np.int64)
    assert support.compare([np.zeros(4, np.int64)], ref) == (1, {"support_gap": (1 << 31, 0)})
