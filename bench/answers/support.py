"""Per-edge triangle support: how many triangles hold each edge, int64,
one number per edge of the forward orientation by ``(degree, id)``, in
``(src, dst)`` order (the order of the program's oriented CSR).

The reference is the benchmark's own, from SciPy alone: with ``A`` the
oriented adjacency (``bench/reference.py``) and ``S = A + A.T`` the
undirected one, the support of oriented edge ``(u, v)`` is
``|N(u) ∩ N(v)| = (S @ S)[u, v]``, so the answer is ``(S @ S) o A`` read
at every entry of ``A``; the products are formed in blocks of rows.

Compared: ``support_gap``, the largest ``|answer[e] - reference[e]|``
over all edges and all of the window's answers; support is exact, so the
limit is 0, and an answer of the wrong shape reads 2**31.  Control: the
base-edge-only support ``(A @ A.T) o A``, which bills each edge only for
``|N+(u) ∩ N+(v)|``, the triangles in which it is the lowest edge, and
leaves out the arm and closure billing (its sum is the triangle count,
not three times it).
"""
import numpy as np

from bench import reference as ref_impl

BLOCK_ROWS = 1 << 12
WRONG_SHAPE = 1 << 31


def answer(counter, csr):
    return counter.edge_support(csr)


def masked_product(a, left, right) -> np.ndarray:
    """``(left @ right) o a`` at every entry of ``a``, in ``(row, column)``
    order, int64; entries where the product is 0 included."""
    out = []
    for s in range(0, a.shape[0], BLOCK_ROWS):
        a_blk = a[s:s + BLOCK_ROWS]
        # a_blk's own ones keep every entry present where the product is 0
        m = (a_blk + (left[s:s + BLOCK_ROWS] @ right).multiply(a_blk)).tocsr()
        m.sort_indices()
        if m.nnz != a_blk.nnz:
            raise AssertionError("the masked product lost or gained entries")
        out.append(m.data - 1)
    return np.concatenate(out).astype(np.int64) if out else np.zeros(0, np.int64)


def reference(edges, n_nodes):
    a = ref_impl.oriented(edges, n_nodes)
    s = (a + a.T).tocsr()
    return masked_product(a, s, s)


def _gap(a, ref) -> int:
    a = np.asarray(a)
    if a.shape != ref.shape:
        return WRONG_SHAPE
    return int(np.abs(a.astype(np.int64) - ref).max(initial=0))


def compare(answers, ref):
    gaps = [_gap(a, ref) for a in answers]
    return sum(g != 0 for g in gaps), {"support_gap": (max(gaps, default=0), 0)}


def control(edges, n_nodes, seed, counter_args):
    a = ref_impl.oriented(edges, n_nodes)
    return masked_product(a, a, a.T.tocsr())
