"""The exact global triangle count.

Compared: ``count_gap``, the largest ``|answer - reference|`` over the
window's answers; the count is exact, so the limit is 0.  Control: the
program's own DOULION estimate (each edge kept with probability 1/2, the
sampled count scaled by 8), which breaks exactness.
"""
from bench import reference as ref_impl

DOULION_P = 0.5


def answer(counter, csr):
    return counter.count(csr)


def reference(edges, n_nodes):
    return ref_impl.triangle_count(edges, n_nodes)


def compare(answers, ref):
    gaps = [abs(a - ref) for a in answers]
    return sum(g != 0 for g in gaps), {"count_gap": (max(gaps, default=0), 0)}


def control(edges, n_nodes, seed, counter_args):
    from repro.core import count_triangles_doulion

    return count_triangles_doulion(edges, p=DOULION_P, seed=seed % (1 << 32), **counter_args)
