"""Graph generators, one module per family, found by a configuration's
``graph`` name, and the per-seed relabelling every run applies.

A generator module has ``generate(params) -> (edges, n_nodes)``: a
canonical ``(2m, 2)`` int32 edge array (every undirected edge once per
direction, no loops, no duplicates) and the vertex count.  The graph is
fixed by the configuration; ``--seed`` draws only the vertex labels.
"""
from __future__ import annotations

import importlib

import numpy as np


def generate(config: dict) -> tuple[np.ndarray, int]:
    module = importlib.import_module(f"bench.graphs.{config['graph']}")
    return module.generate(config["params"])


def rng(seed: int) -> np.random.Generator:
    """The run's generator; any whole number, negative or past 64 bits, is a seed."""
    return np.random.default_rng(np.random.SeedSequence(int(seed) % (1 << 64)))


def relabel_permutation(edges: np.ndarray, n_nodes: int, seed: int) -> np.ndarray:
    """``new_id[old_id]``: a random relabelling that keeps the work unchanged.

    The program orients each edge from the lower ``(degree, id)`` endpoint,
    so a relabelling that keeps the id order among vertices of equal
    degree gives an isomorphic oriented graph: the same out-degrees, the
    same edges per bucket and so the same compiled shapes, with every
    vertex, row and panel elsewhere in memory.  Vertices of different
    degrees are interleaved at random.
    """
    deg = np.bincount(edges[:, 0], minlength=n_nodes)
    ids = np.arange(n_nodes)
    draw = rng(seed).permutation(n_nodes)
    by_id = np.lexsort((ids, deg))       # each degree class, in id order
    by_draw = np.lexsort((draw, deg))    # each degree class, in drawn order
    new_id = np.empty(n_nodes, np.int64)
    new_id[by_id] = draw[by_draw]
    return new_id


def relabel(edges: np.ndarray, n_nodes: int, seed: int) -> np.ndarray:
    """The seed's copy of the graph: ``edges`` with every id relabelled."""
    new_id = relabel_permutation(edges, n_nodes, seed).astype(np.int32)
    return new_id[edges]
