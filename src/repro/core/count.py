"""Counting phase of the parallel forward algorithm (paper §II-C, §III-C).

The paper assigns one CUDA thread per directed edge and runs a serial
two-pointer merge over the two sorted adjacency lists.  A serial merge is
the wrong shape for a TPU (data-dependent control flow starves the VPU), so
we provide two TPU-native *exact* schedules:

``wedge_bsearch``
    Expand each directed edge ``(u, v)`` into its wedge candidates
    ``w ∈ N⁺(u)`` and test ``w ∈ N⁺(v)`` with a *batched* branch-free binary
    search (``⌈log₂ L_max⌉`` vectorized steps, all lanes active).  Work is
    ``Σ_u deg⁺(u)² · log`` — the log factor buys full vectorization.

``panel``
    Bucket edges by intersection width, gather fixed-width neighbor panels
    ``A ∈ (B, L_u)``, ``B ∈ (B, L_v)`` and count equal pairs with a tiled
    all-pairs equality reduction — a masked "equality matmul" that saturates
    the 8×128 VPU lanes.  This is the schedule the Pallas kernel
    (:mod:`repro.kernels.triangle_count`) implements; the jnp version here
    is its oracle and CPU fallback.

Both count each triangle exactly once (forward orientation guarantees a
unique apex with two out-edges).

This module holds the *primitives* (wedge expansion, batched binary
search, bucketing, panel gathers).  Orchestration — schedule selection,
memory-bounded edge chunking, uint64 host accumulation, the distributed
composition — lives in :mod:`repro.core.engine`; ``count_triangles``
below is a thin facade over :class:`repro.core.engine.TriangleCounter`.
Measured schedule trade-offs are tabulated in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .preprocess import OrientedCSR, preprocess
from repro.distributed.compression import ensure_fits_int32

__all__ = [
    "WedgePlan",
    "make_wedge_plan",
    "expand_and_close_wedges",
    "expand_and_close_wedges_indexed",
    "segmented_int32_sum",
    "count_wedges_found",
    "count_triangles_csr",
    "count_triangles",
    "per_node_triangles",
    "bucketize_edges",
    "gather_elements",
    "gather_windows",
    "gather_panel_rows",
    "gather_panels",
    "gather_panels_arrays",
    "panel_intersect_count",
    "panel_intersect_per_node",
    "panel_intersect_support",
]


# ---------------------------------------------------------------------------
# wedge_bsearch schedule
# ---------------------------------------------------------------------------


class WedgePlan(NamedTuple):
    """Static sizing for the wedge expansion (host-computed)."""

    total_wedges: int       # padded wedge-buffer length
    n_search_steps: int     # ⌈log2(max out-degree + 1)⌉


def make_wedge_plan(csr: OrientedCSR, pad_to: int | None = None) -> WedgePlan:
    """Compute concrete wedge-buffer sizing from a (host-resident) CSR."""
    out_deg = np.asarray(csr.out_degree)
    src = np.asarray(csr.src)
    total = int(out_deg[src].sum(dtype=np.int64)) if src.size else 0
    max_deg = int(out_deg.max()) if out_deg.size else 0
    steps = max(1, math.ceil(math.log2(max_deg + 1))) if max_deg else 1
    if pad_to is not None:
        total = max(total, pad_to)
    return WedgePlan(total_wedges=max(total, 1), n_search_steps=steps)


def _batched_search(
    col: jax.Array, lo: jax.Array, hi: jax.Array, target: jax.Array, n_steps: int
) -> tuple[jax.Array, jax.Array]:
    """Branch-free batched binary search over ``col[lo:hi]``.

    All of ``lo``/``hi``/``target`` are rank-1 and processed in lockstep;
    each of the ``n_steps`` iterations is one vectorized gather + compare,
    so the VPU stays full regardless of degree skew.  Returns
    ``(found, pos)`` where ``pos`` is the insertion index — the global
    ``col`` index of the match whenever ``found`` is true, which is what
    per-edge attribution (triangle support) scatters against.
    """
    end = hi

    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) >> 1
        below = col[jnp.clip(mid, 0, col.shape[0] - 1)] < target
        lo = jnp.where(active & below, mid + 1, lo)
        hi = jnp.where(active & ~below, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_steps, body, (lo, hi))
    safe = jnp.clip(lo, 0, col.shape[0] - 1)
    return (lo < end) & (col[safe] == target), safe


def _batched_contains(
    col: jax.Array, lo: jax.Array, hi: jax.Array, target: jax.Array, n_steps: int
) -> jax.Array:
    """Is ``target`` in ``col[lo:hi]``? (membership-only view of the search)."""
    found, _ = _batched_search(col, lo, hi, target, n_steps)
    return found


def _expand_close_body(src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps):
    """Shared wedge expansion + closure; returns every per-slot artifact.

    ``(hit, edge_id, u, v, w, w_idx, vw_idx)``: ``edge_id`` is the slot's
    originating edge (local to this chunk), ``w_idx`` the global directed
    edge index of ``(u, w)`` (the wedge arm inside ``col``), ``vw_idx``
    the global index of the closing edge ``(v, w)`` found by the search.
    Index values on non-``hit`` slots are clipped-safe garbage.
    """
    m_local = src_e.shape[0]
    valid_e = src_e >= 0
    safe_src = jnp.maximum(src_e, 0)
    safe_dst = jnp.maximum(dst_e, 0)
    reps = jnp.where(valid_e, out_deg[safe_src], 0)
    starts = jnp.cumsum(reps) - reps
    edge_id = jnp.repeat(
        jnp.arange(m_local, dtype=jnp.int32), reps, total_repeat_length=wedge_budget
    )
    pos = jnp.arange(wedge_budget, dtype=jnp.int32) - starts[edge_id]
    valid = (pos >= 0) & (pos < reps[edge_id])
    u = safe_src[edge_id]
    v = safe_dst[edge_id]
    w_idx = jnp.clip(row_offsets[u] + pos, 0, col.shape[0] - 1)
    w = col[w_idx]
    found, vw_idx = _batched_search(col, row_offsets[v], row_offsets[v + 1], w, n_steps)
    return found & valid, edge_id, u, v, w, w_idx, vw_idx


def expand_and_close_wedges(src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps):
    """Expand a (possibly −1-padded) directed-edge array into wedges and
    close them with the batched binary search.

    The single shared implementation of the wedge schedule's inner body —
    used unchunked here (:func:`count_wedges_found`) and per budget-sized
    chunk by :mod:`repro.core.engine`.  Returns ``(hit, u, v, w)`` where
    ``hit[i]`` marks wedge slot ``i`` as a closed, non-padding triangle.
    ``wedge_budget`` (static) is the buffer length; padding slots and −1
    edge slots contribute ``hit = False``.
    """
    hit, _, u, v, w, _, _ = _expand_close_body(
        src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps
    )
    return hit, u, v, w


def expand_and_close_wedges_indexed(
    src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps
):
    """Wedge closure with *edge-index* attribution (per-edge support).

    Like :func:`expand_and_close_wedges`, but instead of the triangle's
    vertices each hit slot reports the three **directed edge indices** of
    the triangle it closes: ``(hit, edge_id, uw_idx, vw_idx)`` where
    ``edge_id`` is the originating edge ``(u, v)`` local to this chunk
    (add the chunk's global offset before scattering), ``uw_idx`` is the
    global ``col`` index of the wedge arm ``(u, w)`` and ``vw_idx`` the
    global index of the closing edge ``(v, w)``.  This is the primitive
    under :mod:`repro.analytics.support` — every closed wedge contributes
    one unit of support to exactly those three edges.
    """
    hit, edge_id, _, _, _, w_idx, vw_idx = _expand_close_body(
        src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps
    )
    return hit, edge_id, w_idx, vw_idx


def segmented_int32_sum(hits: jax.Array, seg: int = 1 << 20) -> jax.Array:
    """Reduce a boolean hit buffer to per-``seg``-slot int32 partials.

    A segment sum never exceeds ``seg`` (default 2²⁰), so int32 stays safe
    even when the whole buffer holds ≥ 2³¹ hits; the final reduction runs
    on host in uint64 (:func:`repro.core.engine.accumulate_partials`).
    Shared by the unchunked, chunked, and distributed counting paths.
    """
    n = hits.shape[0]
    pad = (-n) % seg
    padded = jnp.concatenate([hits, jnp.zeros((pad,), hits.dtype)]) if pad else hits
    return jnp.sum(padded.reshape(-1, seg).astype(jnp.int32), axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("plan",))
def count_wedges_found(csr: OrientedCSR, plan: WedgePlan) -> tuple[jax.Array, jax.Array]:
    """Return (found mask over the wedge buffer, wedge endpoints (u,v,w)).

    The wedge buffer enumerates, for each directed edge ``(u, v)``, every
    candidate ``w ∈ N⁺(u)``; ``found[i]`` says wedge ``i`` closes into a
    triangle.  Padding slots are masked off.
    """
    found, u, v, w = expand_and_close_wedges(
        csr.src, csr.col, csr.row_offsets, csr.col, csr.out_degree,
        plan.total_wedges, plan.n_search_steps,
    )
    return found, (u, v, w)


def count_triangles_csr(csr: OrientedCSR, plan: WedgePlan | None = None) -> int:
    """Total triangle count from an oriented CSR (host-orchestrated)."""
    if plan is None:
        plan = make_wedge_plan(csr)
    found, _ = count_wedges_found(csr, plan)
    # Per-2^20-segment int32 partials; the final accumulation happens on
    # host in uint64, so counts like the paper's 8.8e9 (Kronecker-21) do
    # not overflow 32-bit device arithmetic.
    partials = segmented_int32_sum(found)
    return int(np.asarray(partials).astype(np.uint64).sum())


def per_node_triangles(csr: OrientedCSR, plan: WedgePlan | None = None) -> jax.Array:
    """Number of triangles each vertex participates in (for clustering)."""
    if plan is None:
        plan = make_wedge_plan(csr)
    found, (u, v, w) = count_wedges_found(csr, plan)
    inc = found.astype(jnp.int32)
    n = csr.n_nodes
    out = jnp.zeros((n,), jnp.int32)
    out = out.at[u].add(inc)
    out = out.at[v].add(inc)
    out = out.at[w].add(inc)
    return out


# ---------------------------------------------------------------------------
# panel schedule (bucketed fixed-width intersection)
# ---------------------------------------------------------------------------


def bucketize_edges(
    csr: OrientedCSR, widths: tuple[int, ...] = (16, 64, 256, 1024, 4096)
) -> dict[int, np.ndarray]:
    """Group directed edges by the padded width of the *longer* endpoint list.

    Host-side: returns ``{width: edge_indices}``.  Widths are the TPU
    analogue of the paper's warp-size tuning — each bucket compiles to a
    fixed-tile kernel with bounded padding waste.
    """
    out_deg = np.asarray(csr.out_degree)
    src = np.asarray(csr.src)
    col = np.asarray(csr.col)
    # bucket indices are stored int32: fail loudly at m >= 2^31 instead of
    # letting .astype wrap them (satellite of the overflow-discipline pass)
    ensure_fits_int32(src.shape[0], "directed edge count (panel bucket indices)")
    need = np.maximum(out_deg[src], out_deg[col])
    buckets: dict[int, np.ndarray] = {}
    lo = 0
    for w in widths:
        mask = (need > lo) & (need <= w)
        idx = np.nonzero(mask)[0]
        if idx.size:
            buckets[w] = idx.astype(np.int32)
        lo = w
    if (need > widths[-1]).any():
        raise ValueError(
            f"max out-degree {int(need.max())} exceeds largest bucket {widths[-1]}; "
            "widen `widths` (forward orientation bounds it by sqrt(2m))"
        )
    return buckets


LANES = 128  # a TPU vector register row: the unit the window gather moves


def gather_elements(col: jax.Array, base: jax.Array, length: jax.Array, width: int):
    """Rows ``col[base : base + length]``, −1 padded to ``width`` — ``(B, width)``.

    One single-element gather of ``B·width`` slots.  The panel gather of
    every backend but the TPU (see :func:`gather_panel_rows`).
    """
    lane = jnp.arange(width, dtype=jnp.int32)
    idx = jnp.clip(base[:, None] + lane[None, :], 0, col.shape[0] - 1)
    return jnp.where(lane[None, :] < length[:, None], col[idx], -1)


def gather_windows(col: jax.Array, base: jax.Array, length: jax.Array, width: int):
    """Rows ``col[base : base + length]``, −1 padded to ``width`` — ``(B, width)``.

    Each window is fetched as ``k`` whole 128-lane rows of ``col`` viewed
    as ``(·, 128)`` — a ``(B, k)`` row-index gather, the embedding-lookup
    form — and then shifted left by ``base % 128`` with a seven-step
    barrel shift of static lane rolls.  Lanes at or past ``length`` read
    −1.  The panel gather on a TPU (see :func:`gather_panel_rows`).
    Fetching the rows as one ``(k, 128)`` dynamic slice per window
    compiles far faster but ran 2.5–10× slower on a v5e (PERF.md).
    """
    k = -(-width // LANES) + 1   # rows covering any window of `width` slots
    m = col.shape[0]
    n_rows = -(-m // LANES) + k
    rows2d = jnp.pad(col, (0, n_rows * LANES - m), constant_values=-1)
    rows2d = rows2d.reshape(n_rows, LANES)
    base = jnp.clip(base, 0, m)
    row = base // LANES
    x = rows2d[row[:, None] + jnp.arange(k, dtype=row.dtype)[None, :]]
    x = x.reshape(base.shape[0], k * LANES)
    shift = base % LANES
    for bit in range(LANES.bit_length() - 1):
        moved = jnp.roll(x, -(1 << bit), axis=1)
        x = jnp.where(((shift >> bit) & 1)[:, None] == 1, moved, x)
    lane = jnp.arange(width, dtype=jnp.int32)
    return jnp.where(lane[None, :] < length[:, None], x[:, :width], -1)


def gather_panel_rows(col: jax.Array, base: jax.Array, length: jax.Array, width: int):
    """The panel gather, chosen by the platform the program is lowered for.

    A TPU runs :func:`gather_windows`; every other backend runs
    :func:`gather_elements`.  Both return the same array.  On a TPU v5e
    XLA's single-element gather moves a few times 1e7 slots per second,
    4–80× slower than whole-row slices; on XLA's CPU backend it is the
    faster form (``benchmarks/gather_probe.py``; PERF.md).
    """
    return jax.lax.platform_dependent(
        col, base, length,
        tpu=functools.partial(gather_windows, width=width),
        default=functools.partial(gather_elements, width=width),
    )


def add_elements(out: jax.Array, base: jax.Array, rows: jax.Array):
    """``out[base[i] + j] += rows[i, j]`` for every row ``i`` and lane ``j``.

    One single-element scatter-add of ``B·w`` slots; lanes that fall past
    ``out``'s end must carry zero.  The window scatter of every backend
    but the TPU (see :func:`add_panel_rows`).
    """
    lane = jnp.arange(rows.shape[1], dtype=jnp.int32)
    idx = jnp.clip(base[:, None] + lane[None, :], 0, out.shape[0] - 1)
    return out.at[idx].add(rows)


def add_windows(out: jax.Array, base: jax.Array, rows: jax.Array):
    """:func:`add_elements` as whole 128-lane rows: the mirror of
    :func:`gather_windows`.

    Each ``(w,)`` row is padded to ``k`` 128-lane rows, shifted right by
    ``base % 128`` with a seven-step barrel shift of static lane rolls,
    and added to ``k`` whole rows of ``out`` viewed as ``(·, 128)`` — a
    ``(B, k)`` row-index scatter-add, the embedding-gradient form.
    Lanes that fall past ``out``'s end land in padding and are dropped.
    """
    m, width = out.shape[0], rows.shape[1]
    k = -(-width // LANES) + 1   # rows covering any window of `width` slots
    n_rows = -(-m // LANES) + k
    x = jnp.pad(rows, ((0, 0), (0, k * LANES - width)))
    base = jnp.clip(base, 0, m)
    shift = base % LANES
    for bit in range(LANES.bit_length() - 1):
        moved = jnp.roll(x, 1 << bit, axis=1)
        x = jnp.where(((shift >> bit) & 1)[:, None] == 1, moved, x)
    row = base // LANES
    rows2d = jnp.pad(out, (0, n_rows * LANES - m)).reshape(n_rows, LANES)
    rows2d = rows2d.at[row[:, None] + jnp.arange(k, dtype=row.dtype)[None, :]].add(
        x.reshape(base.shape[0], k, LANES))
    return rows2d.reshape(-1)[:m]


def add_panel_rows(out: jax.Array, base: jax.Array, rows: jax.Array):
    """The per-edge support scatter's window add, chosen by the platform
    the program is lowered for, as :func:`gather_panel_rows` chooses the
    gather: a TPU runs :func:`add_windows`, every other backend
    :func:`add_elements`.  Both return the same array."""
    return jax.lax.platform_dependent(
        out, base, rows, tpu=add_windows, default=add_elements,
    )


@functools.partial(jax.jit, static_argnames=("width",))
def gather_panels(csr: OrientedCSR, edge_idx: jax.Array, width: int):
    """Gather fixed-width neighbor panels for a bucket of edges.

    Returns ``(a, b, a_len, b_len)`` with ``a: (B, width)`` the out-neighbors
    of each edge's ``u`` (−1 padded) and ``b`` likewise for ``v``.  The
    gathers run as XLA ops *outside* the kernel — the TPU replacement for
    the paper's reliance on the GPU texture cache inside the merge loop.

    ``edge_idx`` slots holding −1 (budget-chunk padding from the engine)
    yield all-(−1) panel rows with zero lengths, which every intersect
    kernel counts as zero.
    """
    valid = edge_idx >= 0
    safe = jnp.maximum(edge_idx, 0)
    u = csr.src[safe]
    v = csr.col[safe]
    a_len = jnp.where(valid, csr.out_degree[u], 0)
    b_len = jnp.where(valid, csr.out_degree[v], 0)
    a = gather_panel_rows(csr.col, csr.row_offsets[u], a_len, width)
    b = gather_panel_rows(csr.col, csr.row_offsets[v], b_len, width)
    return a, b, a_len, b_len


@functools.partial(jax.jit, static_argnames=("width",))
def gather_panels_arrays(row_offsets, col, out_degree, u, v, width: int):
    """Gather fixed-width neighbor panels for arbitrary ``(u, v)`` pairs.

    The raw-arrays generalization of :func:`gather_panels`: instead of
    indexing a CSR's own directed edge list, the query endpoints are
    given directly, so the same gather serves the engine's directed-edge
    workload, the truss peel's filtered sub-CSRs and the incremental
    service's probe pairs against an *undirected* adjacency.  ``u``/``v``
    slots holding −1 (chunk padding) yield all-(−1) panel rows with zero
    lengths, which every intersect kernel counts as zero.
    """
    valid = (u >= 0) & (v >= 0)
    safe_u = jnp.maximum(u, 0)
    safe_v = jnp.maximum(v, 0)
    a_len = jnp.where(valid, out_degree[safe_u], 0)
    b_len = jnp.where(valid, out_degree[safe_v], 0)
    a = gather_panel_rows(col, row_offsets[safe_u], a_len, width)
    b = gather_panel_rows(col, row_offsets[safe_v], b_len, width)
    return a, b, a_len, b_len


@jax.jit
def panel_intersect_count(a: jax.Array, b: jax.Array) -> jax.Array:
    """Sorted-set intersection sizes via all-pairs equality (jnp oracle).

    ``a: (B, Lu)``, ``b: (B, Lv)``, −1 padding.  O(Lu·Lv) compares but every
    compare is a full-width VPU op; with √(2m)-bounded lists and bucketing
    the constant is small.  The Pallas kernel computes exactly this.
    """
    eq = a[:, :, None] == b[:, None, :]
    valid = (a[:, :, None] >= 0) & (b[:, None, :] >= 0)
    return jnp.sum(eq & valid, axis=(1, 2), dtype=jnp.int32)


@jax.jit
def panel_intersect_per_node(a: jax.Array, b: jax.Array):
    """(count, arm) jnp oracle — the per-node reduction of the eq cube.

    ``arm[i, j]`` counts matches of ``a[i, j]`` inside row ``b[i]`` (0 on
    padding), so scattering ``count`` to the edge endpoints and ``arm``
    to the ``a`` *values* yields per-node triangle incidences.  The
    Pallas rendition is ``intersect_per_node_pallas``.
    """
    eq = a[:, :, None] == b[:, None, :]
    valid = (a[:, :, None] >= 0) & (b[:, None, :] >= 0)
    arm = jnp.sum(eq & valid, axis=2, dtype=jnp.int32)
    return jnp.sum(arm, axis=1, dtype=jnp.int32), arm


@jax.jit
def panel_intersect_support(a: jax.Array, b: jax.Array):
    """(count, arm, closure) jnp oracle — the full support attribution.

    ``closure[i, k]`` counts matches of ``b[i, k]`` inside row ``a[i]``;
    together with ``arm`` every hit is billed to the triangle's three
    directed edges.  The Pallas rendition is ``intersect_support_pallas``.
    """
    eq = a[:, :, None] == b[:, None, :]
    valid = (a[:, :, None] >= 0) & (b[:, None, :] >= 0)
    masked = eq & valid
    arm = jnp.sum(masked, axis=2, dtype=jnp.int32)
    closure = jnp.sum(masked, axis=1, dtype=jnp.int32)
    return jnp.sum(arm, axis=1, dtype=jnp.int32), arm, closure


def _count_panel(csr: OrientedCSR, kernel=None) -> int:
    """Bucketed panel counting; `kernel` overrides the per-bucket intersect.

    Retained for direct-CSR callers; the chunked production path lives in
    :class:`repro.core.engine.TriangleCounter`, which wraps this same
    bucket loop under a wedge-buffer budget.
    """
    intersect = kernel or (lambda a, b, al, bl: panel_intersect_count(a, b))
    total = np.uint64(0)
    for width, idx in bucketize_edges(csr).items():
        a, b, al, bl = gather_panels(csr, jnp.asarray(idx), width)
        counts = intersect(a, b, al, bl)
        total += np.asarray(counts).astype(np.uint64).sum()
    return int(total)


# ---------------------------------------------------------------------------
# public entry point (thin facade over the unified engine)
# ---------------------------------------------------------------------------


def count_triangles(
    edges,
    n_nodes: int | None = None,
    method: str = "wedge_bsearch",
    max_wedge_chunk: int | None = None,
) -> int:
    """Count triangles in a canonical edge array.

    ``method`` ∈ {"auto", "wedge_bsearch", "panel", "pallas"}.  Routes
    through :class:`repro.core.engine.TriangleCounter`; pass
    ``max_wedge_chunk`` to bound the device wedge buffer (memory-bounded
    edge partitioning — see the engine docstring).
    """
    from .engine import TriangleCounter  # late import: engine uses this module

    return TriangleCounter(method=method, max_wedge_chunk=max_wedge_chunk).count(
        edges, n_nodes=n_nodes
    )
