"""Graph500 Kronecker (R-MAT) graphs, the benchmark's own copy.

``generate`` is the program's ``repro.graphs.kronecker_rmat`` followed by
its canonicalization, copied so that the yardstick does not move when the
program does.  At the same parameters it gives the program's edges bit for
bit (``bench/tests/test_graphs.py``).

Parameters (a configuration's ``params``): ``scale`` (``n = 2**scale``),
``edge_factor`` (edge samples per vertex before deduplication), the
initiator probabilities ``a``, ``b``, ``c`` and the generator ``seed``.
"""
from __future__ import annotations

import numpy as np


def kronecker_rmat(scale: int, edge_factor: int, a: float, b: float, c: float,
                   seed: int) -> np.ndarray:
    """Raw ``(edge_factor * 2**scale, 2)`` R-MAT edge samples, labels permuted."""
    rng = np.random.default_rng(seed)
    n_edges = edge_factor << scale
    src = np.zeros(n_edges, dtype=np.int64)
    dst = np.zeros(n_edges, dtype=np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for bit in range(scale):
        r1 = rng.random(n_edges)
        r2 = rng.random(n_edges)
        src_bit = r1 > ab
        dst_bit = r2 > np.where(src_bit, c_norm, a_norm)
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    # permute vertex labels so that degree does not correlate with id
    perm = rng.permutation(1 << scale)
    return np.stack([perm[src], perm[dst]], axis=1)


def canonicalize(edges: np.ndarray) -> np.ndarray:
    """Drop self loops and duplicates; every undirected edge twice, int32.

    Forward pairs ``(lo, hi)`` in packed-key order, then the reversed
    block: the program's canonical edge array.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if edges.size and (edges.min() < 0 or edges.max() > 2**31 - 1):
        raise ValueError("node ids must lie in [0, 2**31)")
    edges = edges[edges[:, 0] != edges[:, 1]]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    key = np.unique(lo << np.int64(32) | hi)
    lo = (key >> np.int64(32)).astype(np.int32)
    hi = (key & np.int64(0xFFFFFFFF)).astype(np.int32)
    fwd = np.stack([lo, hi], axis=1)
    return np.concatenate([fwd, fwd[:, ::-1]], axis=0)


def generate(params: dict) -> tuple[np.ndarray, int]:
    """``(canonical edge array, n_nodes)`` of the configured graph."""
    scale = int(params["scale"])
    raw = kronecker_rmat(scale, int(params["edge_factor"]), float(params["a"]),
                         float(params["b"]), float(params["c"]), int(params["seed"]))
    return canonicalize(raw), 1 << scale
