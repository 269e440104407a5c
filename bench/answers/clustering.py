"""LDBC Graphalytics' local clustering coefficient of every vertex, float64.

Compared: ``lcc_gap``, the largest ``|answer[v] - reference[v]|`` over
all vertices and all of the window's answers.  Both sides compute the
correctly rounded ``2 T(v) / (d(v) (d(v) - 1))`` from exact integers, so
the limit is 0; an answer of the wrong shape, or holding NaN, reads 1,
the widest gap two coefficients can have.  Control: the reference
computed in float32, the precision below the one the configuration
states.
"""
import numpy as np

from bench import reference as ref_impl


def answer(counter, csr):
    return counter.clustering(csr)


def reference(edges, n_nodes):
    return ref_impl.local_clustering(edges, n_nodes)


def _gap(a, ref) -> float:
    a = np.asarray(a, dtype=np.float64)
    if a.shape != ref.shape or np.isnan(a).any():
        return 1.0
    return float(np.abs(a - ref).max(initial=0.0))


def compare(answers, ref):
    gaps = [_gap(a, ref) for a in answers]
    return sum(g != 0 for g in gaps), {"lcc_gap": (max(gaps, default=0.0), 0.0)}


def control(edges, n_nodes, seed, counter_args):
    return ref_impl.local_clustering(edges, n_nodes, dtype=np.float32)
