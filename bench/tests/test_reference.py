"""The reference against brute force, and against the program on karate."""
import itertools

import numpy as np
import pytest

from bench import reference
from bench.graphs import kronecker
from bench.tests.test_work import karate


def brute(edges, n):
    adj = np.zeros((n, n), bool)
    adj[edges[:, 0], edges[:, 1]] = True
    tri = np.zeros(n, np.int64)
    for a, b, c in itertools.combinations(range(n), 3):
        if adj[a, b] and adj[b, c] and adj[a, c]:
            tri[[a, b, c]] += 1
    return tri


@pytest.mark.parametrize("scale", [5, 6])
def test_per_node_and_count_match_brute_force(scale):
    edges, n = kronecker.generate(dict(scale=scale, edge_factor=8, a=0.57, b=0.19,
                                       c=0.19, seed=scale))
    tri = brute(edges, n)
    assert np.array_equal(reference.per_node_triangles(edges, n), tri)
    assert reference.triangle_count(edges, n) == tri.sum() // 3


def test_karate():
    edges, n = karate()
    assert reference.triangle_count(edges, n) == 45
    lcc = reference.local_clustering(edges, n)
    deg = np.bincount(edges[:, 0], minlength=n)
    tri = reference.per_node_triangles(edges, n)
    want = [2 * t / (d * (d - 1)) if d > 1 else 0.0 for t, d in zip(tri, deg)]
    assert np.array_equal(lcc, np.array(want))


def test_blocks_change_nothing(monkeypatch):
    edges, n = kronecker.generate(dict(scale=9, edge_factor=16, a=0.57, b=0.19,
                                       c=0.19, seed=3))
    whole = reference.per_node_triangles(edges, n)
    monkeypatch.setattr(reference, "BLOCK_ROWS", 37)
    assert np.array_equal(reference.per_node_triangles(edges, n), whole)
    assert reference.triangle_count(edges, n) == whole.sum() // 3
