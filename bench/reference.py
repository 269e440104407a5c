"""The plain reference: triangles by sparse matrix products, on the host.

Independent of the program: it takes the benchmark's own edge array and
uses SciPy alone.  Each undirected edge is oriented from the lower
``(degree, id)`` endpoint, which gives the adjacency ``A`` (``A[x, y] = 1``
for ``x -> y``).  A triangle ``u -> v -> w`` with ``u -> w`` has a low
``u``, a middle ``v`` and a top ``w``, and appears exactly once in each of

    M1 = A o (A @ A)      at (u, w): rows give the low, columns the top,
    M2 = A o (A.T @ A)    at (v, w): rows give the middle,

where ``o`` is the element-wise product.  The products are formed in
blocks of rows, so memory stays at one block's worth.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

BLOCK_ROWS = 1 << 14


def oriented(edges: np.ndarray, n_nodes: int) -> sp.csr_matrix:
    """``A``: the forward-oriented adjacency of a canonical edge array."""
    u = edges[:, 0].astype(np.int64)
    v = edges[:, 1].astype(np.int64)
    deg = np.bincount(u, minlength=n_nodes)
    keep = (deg[u] < deg[v]) | ((deg[u] == deg[v]) & (u < v))
    ones = np.ones(int(keep.sum()), np.int64)
    return sp.csr_matrix((ones, (u[keep], v[keep])), shape=(n_nodes, n_nodes))


def _blocks(a: sp.csr_matrix):
    for s in range(0, a.shape[0], BLOCK_ROWS):
        yield s, a[s:s + BLOCK_ROWS]


def triangle_count(edges: np.ndarray, n_nodes: int) -> int:
    """The exact number of triangles."""
    a = oriented(edges, n_nodes)
    at = a.T.tocsr()
    total = 0
    for s, a_blk in _blocks(a):
        total += int((at[s:s + BLOCK_ROWS] @ a).multiply(a_blk).sum())
    return total


def per_node_triangles(edges: np.ndarray, n_nodes: int) -> np.ndarray:
    """Triangles at each vertex, int64 ``(n_nodes,)``."""
    a = oriented(edges, n_nodes)
    at = a.T.tocsr()
    tri = np.zeros(n_nodes, np.int64)
    for s, a_blk in _blocks(a):
        m1 = (a_blk @ a).multiply(a_blk).tocsr()
        m2 = (at[s:s + BLOCK_ROWS] @ a).multiply(a_blk).tocsr()
        rows = slice(s, s + a_blk.shape[0])
        tri[rows] += np.asarray(m1.sum(axis=1)).ravel()      # low
        tri += np.asarray(m1.sum(axis=0)).ravel()            # top
        tri[rows] += np.asarray(m2.sum(axis=1)).ravel()      # middle
    return tri


def local_clustering(edges: np.ndarray, n_nodes: int, dtype=np.float64) -> np.ndarray:
    """LDBC Graphalytics' LCC: ``2 T(v) / (d(v) (d(v) - 1))``, 0 where ``d(v) < 2``."""
    deg = np.bincount(edges[:, 0], minlength=n_nodes).astype(np.int64)
    tri = per_node_triangles(edges, n_nodes)
    pairs = deg * (deg - 1)
    num = (2 * tri).astype(dtype)
    den = np.maximum(pairs, 1).astype(dtype)
    return np.where(pairs > 0, num / den, dtype(0)).astype(dtype)
