"""The generators: the Kronecker copy equals the program's, the RGG one
matches DIMACS10's edge count and a brute-force neighbour list, and the
per-seed relabelling keeps the oriented work unchanged."""
import numpy as np
import pytest

from bench import graphs, work
from bench.graphs import kronecker, rgg

KRON12 = dict(scale=12, edge_factor=16, a=0.57, b=0.19, c=0.19, seed=1503)


def test_kronecker_copy_equals_program():
    from repro.graphs import kronecker_rmat

    edges, n = kronecker.generate(KRON12)
    assert n == 1 << 12
    assert np.array_equal(edges, kronecker_rmat(12, 16, seed=1503))


def test_rgg_matches_brute_force():
    n = 1 << 12
    xy = rgg.points(n, 0)
    r = rgg.radius(n, 0.55)
    order, i, j = rgg.geometric_pairs(xy, r)
    got = set(zip(order[i].tolist(), order[j].tolist()))
    got = {(min(a, b), max(a, b)) for a, b in got}
    d2 = ((xy[:, None, :] - xy[None, :, :]) ** 2).sum(-1)
    a, b = np.nonzero(np.triu(d2 <= r * r, k=1))
    assert got == set(zip(a.tolist(), b.tolist()))
    assert len(got) == i.shape[0]            # each pair found once


def test_rgg20_edge_count_near_dimacs10():
    edges, n = rgg.generate(dict(n_log2=20, radius_factor=0.55, seed=0))
    m = edges.shape[0] // 2
    assert n == 1 << 20
    assert abs(m - 6_891_620) <= 0.01 * 6_891_620   # DIMACS10 rgg_n_2_20_s0
    assert m == 6_895_283


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, -3])
def test_relabel_keeps_oriented_work(seed):
    edges, n = kronecker.generate(dict(KRON12, scale=10))
    new = graphs.relabel(edges, n, seed)
    perm = graphs.relabel_permutation(edges, n, seed)
    assert np.array_equal(np.sort(perm), np.arange(n))
    assert np.array_equal(new, perm[edges])
    before = work.oriented_edges(edges, n)
    after = work.oriented_edges(new, n)
    # the same edges are kept, mapped: an isomorphism of the oriented graph
    kept = set(zip(perm[before[1]].tolist(), perm[before[2]].tolist()))
    assert kept == set(zip(after[1].tolist(), after[2].tolist()))
    assert np.array_equal(after[0][perm], before[0])
    for f in (work.intersection_bytes, work.padded_compares):
        assert f(*before) == f(*after)


def test_relabel_seeds_differ():
    edges, n = kronecker.generate(dict(KRON12, scale=10))
    assert not np.array_equal(graphs.relabel(edges, n, 1), graphs.relabel(edges, n, 2))
    assert np.array_equal(graphs.relabel(edges, n, 1), graphs.relabel(edges, n, 1))
