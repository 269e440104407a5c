"""Bytes and compares of an answer, against a per-edge loop on karate."""
import os

import numpy as np

from bench import work
from bench.graphs.kronecker import canonicalize

KARATE = os.path.join(os.path.dirname(__file__), "data", "karate.txt")


def karate():
    return canonicalize(np.loadtxt(KARATE, dtype=np.int64)), 34


def test_counts_match_a_loop_on_karate():
    edges, n = karate()
    adj = {v: set() for v in range(n)}
    for u, v in edges.tolist():
        adj[u].add(v)
    deg = {v: len(adj[v]) for v in adj}
    out = {u: sorted(v for v in adj[u] if (deg[u], u) < (deg[v], v)) for u in adj}
    m = bytes_ = compares = padded = 0
    for u in out:
        for v in out[u]:
            m += 1
            bytes_ += 4 * (len(out[u]) + len(out[v]))
            compares += len(out[u]) * len(out[v])
            width = next(w for w in (16, 64, 256) if max(len(out[u]), len(out[v])) <= w)
            padded += width * width
    assert m == 78
    got = work.oriented_edges(edges, n)
    assert got[1].shape[0] == 78
    assert work.intersection_bytes(*got) == bytes_
    assert work.intersection_compares(*got) == compares
    assert work.padded_compares(*got) == padded


def test_padded_ladder_extends():
    out_deg = np.array([5000, 3, 1])
    src, dst = np.array([1]), np.array([0])
    assert work.padded_compares(out_deg, src, dst) == 16384 ** 2
