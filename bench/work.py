"""Work of one answer, counted from the graph: bytes and compares.

The forward algorithm intersects, for every oriented edge ``(u, v)``, the
out-lists of ``u`` and ``v``.  Reading both lists once is the least
traffic any implementation of that intersection has, whatever gather,
tiling or fusion carries it out:

    bytes    = 4 * sum over oriented edges of (d+(u) + d+(v))   (int32 ids)
    compares = sum over oriented edges of d+(u) * d+(v)          (all pairs)

``padded_compares`` is what an equality-tile kernel does when each edge
is padded to the width of its bucket (the larger out-degree, rounded up
to the next width of a ladder).
"""
from __future__ import annotations

import numpy as np

ID_BYTES = 4
# the engine's panel widths, extended by x4 rungs like its ladder
WIDTHS = (16, 64, 256, 1024, 4096)


def oriented_edges(edges: np.ndarray, n_nodes: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(out_degree, src, dst)`` of the forward orientation by ``(degree, id)``."""
    u = edges[:, 0].astype(np.int64)
    v = edges[:, 1].astype(np.int64)
    deg = np.bincount(u, minlength=n_nodes)
    keep = (deg[u] < deg[v]) | ((deg[u] == deg[v]) & (u < v))
    src, dst = u[keep], v[keep]
    return np.bincount(src, minlength=n_nodes), src, dst


def intersection_bytes(out_degree, src, dst) -> int:
    return ID_BYTES * int((out_degree[src] + out_degree[dst]).sum(dtype=np.int64))


def intersection_compares(out_degree, src, dst) -> int:
    return int((out_degree[src] * out_degree[dst]).sum(dtype=np.int64))


def padded_compares(out_degree, src, dst, widths=WIDTHS) -> int:
    need = np.maximum(out_degree[src], out_degree[dst])
    ladder = list(widths)
    top = int(need.max()) if need.size else 0
    while ladder[-1] < top:
        ladder.append(ladder[-1] * 4)
    width = np.asarray(ladder, np.int64)[np.searchsorted(ladder, need)]
    return int((width * width).sum(dtype=np.int64))
