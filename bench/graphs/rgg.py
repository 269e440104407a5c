"""Random geometric graphs, as DIMACS10's ``rgg_n_2_<k>_s<seed>`` family.

``2**n_log2`` points drawn uniformly in the unit square; an edge joins
two points whose Euclidean distance is at most
``radius_factor * sqrt(ln n / n)`` (0.55 in DIMACS10).  Vertices are
numbered in the order of the grid cell that holds them (row-major cells
of side at least the radius), which keeps neighbours close in id.

Neighbour search is bucketed: each point is compared only with the
points of its own cell and of four neighbouring cells (the half
stencil), so every unordered pair is tested once.  The brute-force
O(n^2) check is ``bench/tests/test_graphs.py``.

Parameters (a configuration's ``params``): ``n_log2``,
``radius_factor`` and the point ``seed``.
"""
from __future__ import annotations

import math

import numpy as np

# the half stencil: own cell, then right, and the three cells of the next row
_OFFSETS = ((0, 0), (1, 0), (-1, 1), (0, 1), (1, 1))


def radius(n: int, radius_factor: float) -> float:
    return radius_factor * math.sqrt(math.log(n) / n)


def points(n: int, seed: int) -> np.ndarray:
    """``(n, 2)`` float64 points, uniform in the unit square."""
    return np.random.default_rng(seed).random((n, 2))


def geometric_pairs(xy: np.ndarray, r: float) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(order, i, j)``: points sorted by grid cell, and the pairs within ``r``.

    ``i < j`` index the sorted points; ``order[k]`` is the input index of
    sorted point ``k``.
    """
    g = max(1, int(1.0 / r))          # cells per side; each side >= r
    cx = np.minimum((xy[:, 0] * g).astype(np.int64), g - 1)
    cy = np.minimum((xy[:, 1] * g).astype(np.int64), g - 1)
    cell = cy * g + cx
    order = np.argsort(cell, kind="stable")
    cell, cx, cy, xy = cell[order], cx[order], cy[order], xy[order]
    start = np.searchsorted(cell, np.arange(g * g + 1))
    r2 = r * r
    parts_i, parts_j = [], []
    idx = np.arange(xy.shape[0], dtype=np.int64)
    for dx, dy in _OFFSETS:
        nx, ny = cx + dx, cy + dy
        ok = (nx >= 0) & (nx < g) & (ny < g)
        src = idx[ok]
        nc = ny[ok] * g + nx[ok]
        lo, hi = start[nc], start[nc + 1]
        if (dx, dy) == (0, 0):
            lo = src + 1                  # same cell: only later points
        reps = np.maximum(hi - lo, 0)
        i = np.repeat(src, reps)
        first = np.repeat(lo - np.cumsum(reps) + reps, reps)
        j = first + np.arange(i.shape[0], dtype=np.int64)
        d = xy[i] - xy[j]
        near = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] <= r2
        parts_i.append(i[near])
        parts_j.append(j[near])
    i = np.concatenate(parts_i)
    j = np.concatenate(parts_j)
    return order, np.minimum(i, j), np.maximum(i, j)


def generate(params: dict) -> tuple[np.ndarray, int]:
    """``(canonical edge array, n_nodes)`` of the configured graph."""
    n = 1 << int(params["n_log2"])
    xy = points(n, int(params["seed"]))
    _, i, j = geometric_pairs(xy, radius(n, float(params["radius_factor"])))
    fwd = np.stack([i, j], axis=1).astype(np.int32)
    return np.concatenate([fwd, fwd[:, ::-1]], axis=0), n
