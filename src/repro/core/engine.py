"""Unified triangle-counting engine with memory-bounded edge partitioning.

:class:`TriangleCounter` puts the four counting schedules that used to be
siloed across :mod:`repro.core.count` and :mod:`repro.core.distributed`
behind one front door::

    from repro.core import TriangleCounter

    tc = TriangleCounter(method="auto", max_wedge_chunk=1 << 22)
    t  = tc.count(edges)          # exact global count (host int, uint64-safe)
    pn = tc.per_node(edges)       # per-vertex triangle incidences
    es = tc.edge_support(edges)   # per-directed-edge triangle support
    cc = tc.clustering(edges)     # local clustering coefficients

Kernel backend registry
=======================

Every workload — global count, per-node incidences, per-edge support —
executes through a :class:`KernelBackend` registered per schedule name
(:func:`register_backend` / :func:`make_backend`).  A backend owns its
*planning* (how a query edge list is cut into budget-obeying chunks) and
its three chunk kernels:

``count_chunk``     → int32 device partials (uint64-accumulated on host)
``per_node_chunk``  → per-vertex int32 scatter for one chunk
``support_chunk``   → per-directed-edge int32 scatter for one chunk

:class:`WedgeBackend` plans fan-out-bounded contiguous edge chunks and
runs the batched-binary-search wedge kernels; :class:`PanelBackend`
(``"panel"``) buckets edges by neighbor-panel width and runs the jnp
equality-tile reductions; :class:`PallasBackend` (``"pallas"``) is the
same plan driving the Pallas kernel family
(:mod:`repro.kernels.triangle_count`), optionally steered by a
:class:`repro.core.tuning.AutoTuner`; :class:`DistributedBackend`
(``"distributed"``) plans §III-E round-robin edge stripes over every
mesh device and merges the striped kernels' partials with collectives —
``psum`` for per-node incidences, a stripe-offset (delta-compressed)
``all_gather`` for per-edge support — so every workload, including the
truss peel and the incremental probes, executes genuinely multi-device.
A backend asked for a workload outside its capability set falls back to
the wedge backend with an explicit ``EngineStats.fallback_reason`` and a
one-time ``RuntimeWarning`` instead of a silent substitution.

The shared driver (:func:`run_workload`) is what the analytics
subsystem (per-edge support, k-truss peeling) and the incremental
service route through as well, so the Pallas fast path serves every
workload, not just scalar counts.

The headline capability is **memory-bounded edge partitioning** — the
reproduction of the paper's "larger than device memory" discipline.  The
paper (§III-C) assigns one CUDA thread per directed edge; the device-side
working set of our TPU rendition is instead the *wedge buffer* of
``Σ deg⁺(u)`` candidate slots, which for an 89M-edge Kronecker graph is
billions of slots — far beyond HBM if materialized at once.  The engine
splits the directed edge list into contiguous chunks whose wedge buffers
fit a static budget, pads every chunk to that budget, and reuses **one**
jitted kernel across all chunks, so the number of *compiles* is constant
while the number of *launches* scales with graph size.  Partial counts
leave the device as int32 and are accumulated on host in uint64
(:func:`accumulate_partials`), so counts like the paper's 3.8B triangles
never overflow 32-bit device arithmetic.

Knob → paper-section map
========================

``method``
    ``"wedge_bsearch"`` / ``"panel"`` / ``"pallas"`` are the TPU-native
    renditions of the paper's ``CountTriangles`` kernel (§II-C forward
    algorithm, §III-C counting phase); ``"distributed"`` is the multi-GPU
    scheme of §III-E (replicated CSR, striped edge list, reduced
    partials); ``"auto"`` picks from graph stats (:func:`choose_method`).
``max_wedge_chunk``
    The per-launch wedge-buffer budget, in candidate slots.  This is the
    engine's analogue of the paper's per-GPU memory ceiling that forces
    the edge list to be processed in passes (§III-E, Table I's 89M-edge
    graph on a 3 GB C2050).  ``None`` materializes one full-size buffer
    (single chunk).  A budget smaller than one edge's fan-out is bumped
    to the max fan-out — a chunk must hold at least one whole edge.
``widths``
    Panel bucket boundaries for the ``panel``/``pallas`` schedules — the
    TPU analogue of the paper's warp-size tuning (§III-D5).  Wedge chunking
    wraps the bucket loop: each bucket is processed in slices of
    ``max_wedge_chunk // width`` edges so panel gathers respect the same
    budget.  Degrees beyond the last rung extend the ladder instead of
    failing.
``mesh``
    A ``jax.sharding.Mesh`` enabling the §III-E multi-device scheme; the
    edge chunking composes with the round-robin striping in
    :mod:`repro.core.distributed` (chunks slice the striped per-shard
    edge axis, so every device's buffer stays within budget).
``tuner``
    A :class:`repro.core.tuning.AutoTuner` steering the Pallas kernels'
    ``(block_edges, TLv)`` tiles from its per-shape grid-search cache —
    the persisted form of the paper's §III-D5 sweep.

Scheduling heuristics (``method="auto"``) follow §III-C's skew
discussion: low max out-degree and low skew favor the panel equality
reduction, heavy tails favor the binary-search schedule, and a multi-chip
mesh always routes to the distributed striping.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import os
import warnings
import weakref
from typing import Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .count import (
    add_panel_rows,
    expand_and_close_wedges,
    expand_and_close_wedges_indexed,
    gather_panels_arrays,
    panel_intersect_count,
    panel_intersect_per_node,
    panel_intersect_support,
    segmented_int32_sum,
)
from .preprocess import (
    OrientedCSR,
    oriented_from_compressed,
    oriented_from_undirected_csr,
    preprocess,
)
from repro.distributed.compression import ensure_fits_int32

__all__ = [
    "TriangleCounter",
    "EngineStats",
    "choose_method",
    "plan_edge_chunks",
    "accumulate_partials",
    "prepare_oriented",
    "degree_histogram",
    "search_steps",
    "next_pow2",
    "iter_wedge_chunks",
    "chunk_count_kernel",
    "chunk_per_node_kernel",
    "chunk_support_kernel",
    "KernelBackend",
    "WedgeBackend",
    "PanelBackend",
    "PallasBackend",
    "DistributedBackend",
    "register_backend",
    "make_backend",
    "resolve_backend",
    "Workload",
    "make_workload",
    "workload_from_csr",
    "WorkPlan",
    "StripedChunk",
    "run_workload",
    "METHODS",
    "CAPABILITIES",
]

METHODS = ("auto", "wedge_bsearch", "panel", "pallas", "distributed")

CAPABILITIES = ("count", "per_node", "support")

DEFAULT_WIDTHS = (16, 64, 256, 1024, 4096)


# ---------------------------------------------------------------------------
# host-side planning + accumulation
# ---------------------------------------------------------------------------


def accumulate_partials(partials) -> int:
    """uint64 host accumulation of device partial counts.

    Device partials are int32 scalars or vectors, each element bounded by
    its reduction segment (2²⁰ slots in the chunk kernels); the *sum*
    over partials can exceed 2³¹ — the paper's Table I counts reach
    3.8B — so the running total lives in uint64 on host.
    """
    total = np.uint64(0)
    for p in partials:
        arr = np.asarray(p)
        if arr.size == 0:
            continue
        total += np.uint64(arr.astype(np.uint64).sum())
    return int(total)


def plan_edge_chunks(reps: np.ndarray, budget: int | None):
    """Greedy contiguous partition of the directed edge list.

    ``reps[i]`` is the wedge fan-out of directed edge ``i``.  Returns
    ``(bounds, effective_budget)`` where every ``[start, end)`` chunk in
    ``bounds`` satisfies ``reps[start:end].sum() <= effective_budget``.
    The effective budget is ``max(budget, reps.max())`` — a chunk must
    hold at least one whole edge's fan-out, so a sub-fan-out budget is
    bumped rather than splitting an adjacency list.
    """
    reps = np.asarray(reps, dtype=np.int64)
    m = reps.shape[0]
    if m == 0:
        return [(0, 0)], 1
    total = int(reps.sum(dtype=np.int64))
    max_fan = int(reps.max())
    if budget is None or budget >= total:
        return [(0, m)], max(total, 1)
    eff = max(int(budget), max_fan, 1)
    cum = np.cumsum(reps)
    bounds = []
    start = 0
    while start < m:
        base = int(cum[start - 1]) if start else 0
        end = int(np.searchsorted(cum, base + eff, side="right"))
        end = max(end, start + 1)
        bounds.append((start, end))
        start = end
    return bounds, eff


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """What the last engine call actually did (for tests and tuning).

    ``resolved_method`` is what configuration + ``"auto"`` dispatch chose;
    ``method`` is what actually executed.  They differ only when the
    resolved backend lacks the requested workload capability — e.g. a
    custom-registered count-only backend asked for per-node — in which
    case the engine runs the wedge backend and says so:
    ``fallback_reason`` holds the human-readable why (and a one-time
    ``RuntimeWarning`` fires), so capability gaps are never silent.
    Stats are cleared at the start of every public engine call, so a
    stale ``fallback_reason`` never outlives the invocation that earned
    it.  ``peak_wedge_buffer`` is the largest buffer a launch actually
    materialized (the max chunk load) — not the requested budget, which
    lives in ``wedge_budget``.

    The stripe fields describe the §III-E partition when the distributed
    backend executed (``n_stripes > 1``): ``stripe_skew`` is
    ``max/mean`` wedge load over stripes (the distributed collectives
    are synchronous, so load skew *is* timing skew — see
    :func:`repro.distributed.straggler.stripe_skew_report`), and
    ``straggler_stripe`` the stripe the median+MAD rule flags (usually
    ``None``: round-robin striping balances skewed degree
    distributions).

    ``timings`` breaks the call's wall clock into host phases (seconds):
    ``preprocess`` / ``host_copy`` / ``plan`` / ``dispatch`` / ``wait`` /
    ``fold``.  Each is the duration of the ``repro.obs`` span of the same
    phase (``engine.<phase>``), from the same two clock reads, so the
    timings and the spans agree by construction.  ``dispatch`` is the
    enqueue of every chunk's copies and kernels; ``wait`` is the host
    blocked until the device's partials are ready, which includes
    whatever device work the enqueue did not overlap; the device's own
    time per operation comes from a profiler trace, where the spans
    appear as ``tc.engine.<phase>``.
    """

    method: str                  # executed schedule, never "auto"
    resolved_method: str         # configured/dispatched schedule, never "auto"
    n_chunks: int                # device launches for the counting phase
    peak_wedge_buffer: int       # largest buffer materialized per launch
    wedge_budget: int | None     # requested budget (None = unbounded)
    total_wedges: int            # Σ fan-out over all directed edges
    n_directed_edges: int
    fallback_reason: str | None = None  # why method != resolved_method
    n_stripes: int = 1                  # §III-E stripes (1 = single device)
    stripe_skew: float | None = None    # max/mean stripe wedge load
    straggler_stripe: int | None = None  # stripe flagged by the MAD rule
    timings: dict | None = None          # phase → seconds (see above)


# ---------------------------------------------------------------------------
# chunk kernels (compiled once per (shape-budget, steps) pair, reused
# across every chunk — chunk count drives launches, not compiles)
#
# These, together with `iter_wedge_chunks` / `search_steps` /
# `prepare_oriented` below, are the engine's *stable internal API*: the
# plumbing other subsystems (repro.core.incremental, repro.analytics)
# build chunked wedge workloads from, instead of growing private copies.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("wedge_budget", "n_steps"))
def chunk_count_kernel(src_e, dst_e, row_offsets, col, out_deg, *, wedge_budget, n_steps):
    """Count triangles closed by one −1-padded edge chunk.

    Returns a *vector* of int32 partials, one per 2²⁰-slot segment of the
    wedge buffer (:func:`repro.core.count.segmented_int32_sum`): int32 is
    safe even for an unbounded (``max_wedge_chunk=None``) launch whose
    total hits exceed 2³¹ — the final uint64 reduction happens on host.
    """
    hit, _, _, _ = expand_and_close_wedges(
        src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps
    )
    return segmented_int32_sum(hit)


@functools.partial(jax.jit, static_argnames=("wedge_budget", "n_steps"))
def chunk_per_node_kernel(src_e, dst_e, row_offsets, col, out_deg, *, wedge_budget, n_steps):
    """Per-vertex triangle incidences contributed by one edge chunk."""
    hit, u, v, w = expand_and_close_wedges(
        src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps
    )
    inc = hit.astype(jnp.int32)
    n = row_offsets.shape[0] - 1
    out = jnp.zeros((n,), jnp.int32)
    out = out.at[u].add(inc)
    out = out.at[v].add(inc)
    out = out.at[w].add(inc)
    return out


@functools.partial(jax.jit, static_argnames=("wedge_budget", "n_steps"))
def chunk_support_kernel(
    src_e, dst_e, edge_offset, row_offsets, col, out_deg, *, wedge_budget, n_steps
):
    """Per-directed-edge support contributed by one −1-padded edge chunk.

    ``edge_offset`` (traced scalar — no recompile per chunk) is the
    chunk's start index in the global directed edge list; the base
    edge's local id shifts by it, while the arm (``uw``) and closure
    (``vw``) indices from the wedge expansion are global already.
    Returns an int32 vector over the full ``col`` axis.
    """
    hit, edge_id, uw_idx, vw_idx = expand_and_close_wedges_indexed(
        src_e, dst_e, row_offsets, col, out_deg, wedge_budget, n_steps
    )
    inc = hit.astype(jnp.int32)
    m_dir = col.shape[0]
    uv_idx = jnp.clip(edge_offset + edge_id, 0, m_dir - 1)
    out = jnp.zeros((m_dir,), jnp.int32)
    out = out.at[uv_idx].add(inc)
    out = out.at[uw_idx].add(inc)
    out = out.at[vw_idx].add(inc)
    return out


# legacy underscore names (pre-analytics); new code uses the public ones
_chunk_count_kernel = chunk_count_kernel
_chunk_per_node_kernel = chunk_per_node_kernel


@functools.partial(jax.jit, static_argnames=("n_out",))
def _panel_scatter_per_node(u, v, a, count, arm, *, n_out):
    """Scatter a panel chunk's (count, arm) outputs to per-vertex slots.

    ``count`` bills each hit to the edge endpoints ``u``/``v``; ``arm``
    bills it to the third vertex — the *values* of the ``a`` panel.  All
    padding contributes zeros (count/arm are 0 there), so clipped
    indices never corrupt real slots.
    """
    out = jnp.zeros((n_out,), jnp.int32)
    out = out.at[jnp.clip(u, 0, n_out - 1)].add(jnp.where(u >= 0, count, 0))
    out = out.at[jnp.clip(v, 0, n_out - 1)].add(jnp.where(v >= 0, count, 0))
    out = out.at[jnp.clip(a, 0, n_out - 1)].add(arm)
    return out


@functools.partial(jax.jit, static_argnames=("m_out",))
def support_scatter(edge_idx, u, v, row_offsets, count, arm, closure, *, m_out):
    """Scatter (count, arm, closure) to the three directed-edge slots.

    Base ``(u, v)`` is the chunk's global query id; arm slot ``j`` is
    directed edge ``row_offsets[u] + j`` (the wedge arm ``(u, w)``);
    closure slot ``k`` is ``row_offsets[v] + k`` (the closing edge
    ``(v, w)``).  Lanes past a row's true length carry zero counts, so
    their adds are harmless.  A chunk of ``B`` rows scatters
    ``B × (1 + wu + wv)`` lanes, padded rows included; a TPU adds the
    arm and closure rows as whole 128-lane rows (:func:`add_panel_rows`).
    Its device operations read ``jit_support_scatter/...`` in a profiler
    trace.
    """
    out = jnp.zeros((m_out,), jnp.int32)
    out = out.at[jnp.clip(edge_idx, 0, m_out - 1)].add(
        jnp.where(edge_idx >= 0, count, 0)
    )
    out = add_panel_rows(out, row_offsets[jnp.maximum(u, 0)], arm)
    return add_panel_rows(out, row_offsets[jnp.maximum(v, 0)], closure)


def search_steps(csr: OrientedCSR) -> int:
    """⌈log₂(max out-degree + 1)⌉ — the binary-search depth the chunk
    kernels need for this CSR (static argument, shared by all chunks)."""
    max_deg = int(np.asarray(csr.out_degree).max()) if csr.n_nodes else 0
    return max(1, math.ceil(math.log2(max_deg + 1))) if max_deg else 1


def prepare_oriented(edges, n_nodes: int | None = None) -> OrientedCSR | None:
    """Normalize any accepted graph input to an :class:`OrientedCSR`.

    Accepts a pre-built :class:`OrientedCSR` (returned as-is), a
    compressed CSR (anything with ``decode_block``, e.g.
    ``repro.graphs.io.CompressedCSR`` — oriented block-by-block without
    ever materializing the flat ``col``; note per-node/support results
    are then in *relabeled* ids, map back with
    ``CompressedCSR.map_per_node`` / ``new_to_old``), a cached undirected
    CSR (anything with ``row_offsets``/``col``/``n_nodes``, e.g.
    ``repro.graphs.io.CSRGraph`` — oriented by a host-side filter, never
    re-canonicalized), or a canonical edge array (full preprocessing).
    Returns ``None`` for an empty graph.  This is the shared input front
    door of :class:`TriangleCounter` and the analytics subsystem — call
    it once and pass the CSR around to avoid repeated preprocessing.
    """
    if isinstance(edges, OrientedCSR):
        csr = edges
    elif hasattr(edges, "decode_block"):
        csr = oriented_from_compressed(edges)
    elif hasattr(edges, "row_offsets") and hasattr(edges, "col"):
        csr = oriented_from_undirected_csr(
            edges.row_offsets, edges.col, getattr(edges, "n_nodes", None)
        )
    else:
        edges = np.asarray(edges)
        if edges.size == 0:
            return None
        if n_nodes is None:
            n_nodes = int(edges.max()) + 1
        csr = preprocess(jnp.asarray(edges), n_nodes=n_nodes)
    if csr.n_directed_edges > 0:
        return csr
    return None


def degree_histogram(edges, n_nodes: int | None = None) -> tuple[np.ndarray, int]:
    """Undirected degrees + node count for any accepted graph input kind."""
    if isinstance(edges, OrientedCSR):
        return np.asarray(edges.degree, dtype=np.int64), edges.n_nodes
    if hasattr(edges, "decode_block"):
        # compressed CSR: degrees come off the flat row offsets, no decode
        return np.diff(np.asarray(edges.row_offsets)).astype(np.int64), int(
            edges.n_nodes
        )
    if hasattr(edges, "row_offsets") and hasattr(edges, "col"):
        return np.diff(np.asarray(edges.row_offsets)).astype(np.int64), int(
            getattr(edges, "n_nodes", np.asarray(edges.row_offsets).shape[0] - 1)
        )
    edges = np.asarray(edges)
    if edges.size == 0:
        return np.zeros((n_nodes or 0,), np.int64), n_nodes or 0
    if n_nodes is None:
        n_nodes = int(edges.max()) + 1
    return np.bincount(edges[:, 0], minlength=n_nodes).astype(np.int64), n_nodes


def next_pow2(x: int) -> int:
    """Smallest power of two ≥ x (pow2 shape bucketing helper)."""
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


# ---------------------------------------------------------------------------
# workloads: the uniform "query edges vs adjacency" view every backend plans
# ---------------------------------------------------------------------------


class Workload(NamedTuple):
    """One edge-query workload: query pairs closed against an adjacency.

    ``(src_e[i], dst_e[i])`` is query edge ``i`` — the directed edge list
    itself for the engine's count/per-node/support calls, a filtered
    sub-CSR for the truss peel, or probe pairs against an *undirected*
    packed adjacency for the incremental service.  −1 slots are padding.
    ``row_offsets``/``col``/``out_degree`` describe the adjacency rows
    the queries intersect.  The ``*_host`` fields are NumPy views used
    for planning (the originals may live on device and are fed to the
    kernels untouched).
    """

    row_offsets: object
    col: object
    out_degree: object
    src_e: object
    dst_e: object
    src_host: np.ndarray
    dst_host: np.ndarray
    deg_host: np.ndarray
    n_steps: int


def make_workload(row_offsets, col, out_degree, src_e, dst_e, n_steps: int | None = None) -> Workload:
    """Build a :class:`Workload` from raw (host or device) arrays."""
    deg_host = np.asarray(out_degree)
    if n_steps is None:
        max_deg = int(deg_host.max()) if deg_host.size else 0
        n_steps = max(1, math.ceil(math.log2(max_deg + 1))) if max_deg else 1
    return Workload(
        row_offsets, col, out_degree, src_e, dst_e,
        np.asarray(src_e), np.asarray(dst_e), deg_host, n_steps,
    )


def workload_from_csr(csr: OrientedCSR) -> Workload:
    """The engine's standard workload: every directed edge queries its CSR."""
    return make_workload(
        csr.row_offsets, csr.col, csr.out_degree, csr.src, csr.col,
        n_steps=search_steps(csr),
    )


class _DeviceAdj(NamedTuple):
    """Device-resident adjacency arrays shared by every chunk launch."""

    row_offsets: jax.Array
    col: jax.Array
    out_degree: jax.Array
    n_steps: int


class WedgeChunk(NamedTuple):
    """One −1-padded contiguous slice of the query edge list."""

    src: object
    dst: object
    start: int    # offset into the global query list (support scatter)
    buffer: int   # static wedge-buffer length for this launch


class PanelChunk(NamedTuple):
    """One width-bucket slice of the query edge list (−1 padded)."""

    edge_idx: np.ndarray  # global query ids
    u: np.ndarray
    v: np.ndarray
    width: int


class StripedChunk(NamedTuple):
    """One −1-padded column slice of the §III-E striped edge axis."""

    src: np.ndarray   # (n_stripes, cols) round-robin striped sources
    dst: np.ndarray
    start: int        # starting column in the striped axis
    buffer: int       # static per-shard wedge-buffer length


class WorkPlan(NamedTuple):
    """A backend's chunking decision for one workload.

    ``timings`` is filled in by ``run_workload`` on the plan it returns
    (backends leave it at the default): phase → seconds.  ``chunks`` is
    consumed by one run; a kept plan (:class:`_KeptPlans`) holds them as
    a tuple and hands each answer a fresh iterator.
    """

    chunks: Iterator
    n_chunks: int
    peak_buffer: int   # largest per-launch buffer (slots/elements)
    total_wedges: int  # Σ fan-out over the query edges
    n_stripes: int = 1                        # §III-E stripes (distributed)
    stripe_loads: tuple[int, ...] | None = None  # wedge slots per stripe
    timings: dict | None = None                  # filled by run_workload


# ---------------------------------------------------------------------------
# the backends
# ---------------------------------------------------------------------------


class KernelBackend:
    """Protocol each registered schedule implements.

    A backend owns chunk planning (:meth:`plan`) and the three chunk
    kernels.  ``capabilities`` declares which workloads it can execute;
    :func:`resolve_backend` substitutes the wedge backend (recording an
    explicit fallback reason) for anything outside that set.
    """

    name: str = "abstract"
    capabilities: frozenset = frozenset()

    def plan(self, work: Workload, budget: int | None, *, bucket_pow2: bool = False) -> WorkPlan:
        raise NotImplementedError

    def count_chunk(self, adj: _DeviceAdj, chunk):
        raise NotImplementedError

    def per_node_chunk(self, adj: _DeviceAdj, chunk, n_out: int):
        raise NotImplementedError

    def support_chunk(self, adj: _DeviceAdj, chunk, m_out: int):
        raise NotImplementedError


class WedgeBackend(KernelBackend):
    """The batched-binary-search wedge schedule (§II-C forward algorithm).

    Plans greedy contiguous edge chunks whose wedge fan-out totals obey
    the budget (:func:`plan_edge_chunks`); every chunk launches the same
    jitted kernel at one static buffer shape.
    """

    name = "wedge_bsearch"
    capabilities = frozenset(CAPABILITIES)

    def plan(self, work: Workload, budget: int | None, *, bucket_pow2: bool = False) -> WorkPlan:
        src, dst = work.src_host, work.dst_host
        reps = np.where(
            src >= 0, work.deg_host[np.maximum(src, 0)], 0
        ).astype(np.int64)
        bounds, _ = plan_edge_chunks(reps, budget)
        cum = np.concatenate([[0], np.cumsum(reps)])
        peak = max(int(cum[end] - cum[start]) for start, end in bounds)
        peak = max(peak, 1)
        edges_per_chunk = max(end - start for start, end in bounds)
        if bucket_pow2:
            peak = next_pow2(peak)
            edges_per_chunk = next_pow2(edges_per_chunk)

        def gen():
            if len(bounds) == 1 and edges_per_chunk == src.shape[0]:
                # single full chunk: feed the (possibly device-resident)
                # arrays directly — no host round-trip, no copies
                yield WedgeChunk(work.src_e, work.dst_e, 0, peak)
                return
            for start, end in bounds:
                pad = edges_per_chunk - (end - start)
                s, d = src[start:end], dst[start:end]
                if pad:
                    fill = np.full(pad, -1, np.int32)
                    s = np.concatenate([s, fill])
                    d = np.concatenate([d, fill])
                yield WedgeChunk(
                    s.astype(np.int32, copy=False),
                    d.astype(np.int32, copy=False),
                    start, peak,
                )

        return WorkPlan(gen(), len(bounds), peak, int(reps.sum(dtype=np.int64)))

    def count_chunk(self, adj, chunk):
        return chunk_count_kernel(
            jnp.asarray(chunk.src), jnp.asarray(chunk.dst),
            adj.row_offsets, adj.col, adj.out_degree,
            wedge_budget=chunk.buffer, n_steps=adj.n_steps,
        )

    def per_node_chunk(self, adj, chunk, n_out):
        return chunk_per_node_kernel(
            jnp.asarray(chunk.src), jnp.asarray(chunk.dst),
            adj.row_offsets, adj.col, adj.out_degree,
            wedge_budget=chunk.buffer, n_steps=adj.n_steps,
        )

    def support_chunk(self, adj, chunk, m_out):
        return chunk_support_kernel(
            jnp.asarray(chunk.src), jnp.asarray(chunk.dst), np.int32(chunk.start),
            adj.row_offsets, adj.col, adj.out_degree,
            wedge_budget=chunk.buffer, n_steps=adj.n_steps,
        )


class PanelBackend(KernelBackend):
    """The bucketed fixed-width panel schedule (jnp equality tiles).

    Plans width buckets (paper §III-D5 warp-size analogue) sliced under
    ``budget // width`` rows each; chunk kernels gather neighbor panels
    with XLA and reduce the broadcast-equality cube.  Degrees beyond the
    configured ladder extend it by ×4 rungs instead of failing, so any
    adjacency — including the incremental service's unoriented probe
    rows — is servable.
    """

    name = "panel"
    capabilities = frozenset(CAPABILITIES)

    def __init__(self, widths=DEFAULT_WIDTHS, tuner=None):
        self.widths = tuple(widths)
        self.tuner = tuner

    # intersect flavors — PallasBackend overrides with the kernel family
    def intersect_count(self, a, b):
        return panel_intersect_count(a, b)

    def intersect_per_node(self, a, b):
        return panel_intersect_per_node(a, b)

    def intersect_support(self, a, b):
        return panel_intersect_support(a, b)

    def _ladder(self, max_need: int):
        ws = list(self.widths)
        while ws and ws[-1] < max_need:
            ws.append(ws[-1] * 4)
        return tuple(ws)

    def plan(self, work: Workload, budget: int | None, *, bucket_pow2: bool = False) -> WorkPlan:
        src, dst, deg = work.src_host, work.dst_host, work.deg_host
        ensure_fits_int32(src.shape[0], "panel query edge count")
        valid = (src >= 0) & (dst >= 0)
        du = np.where(valid, deg[np.maximum(src, 0)], 0).astype(np.int64)
        dv = np.where(valid, deg[np.maximum(dst, 0)], 0).astype(np.int64)
        need = np.maximum(du, dv)
        total_wedges = int(du.sum(dtype=np.int64))

        def take(arr, sl):
            return np.where(sl >= 0, arr[np.maximum(sl, 0)], -1).astype(np.int32)

        chunks: list[PanelChunk] = []
        peak = 0
        lo = 0
        for w in self._ladder(int(need.max()) if need.size else 0):
            mask = (need > lo) & (need <= w)
            lo = w
            idx = np.nonzero(mask)[0].astype(np.int32)
            if not idx.size:
                continue
            per = len(idx) if budget is None else max(1, int(budget) // w)
            n_slices = -(-len(idx) // per)
            for s in range(0, len(idx), per):
                sl = idx[s : s + per]
                rows = per if n_slices > 1 else len(sl)
                if bucket_pow2:
                    rows = next_pow2(rows)
                pad = rows - len(sl)
                if pad:
                    sl = np.concatenate([sl, np.full(pad, -1, np.int32)])
                chunks.append(PanelChunk(sl, take(src, sl), take(dst, sl), w))
                peak = max(peak, rows * w)

        return WorkPlan(iter(chunks), len(chunks), peak, total_wedges)

    @staticmethod
    def _upload(chunk, *fields):
        """The chunk's index arrays on the device: host arrays are copied
        there, a kept plan's device arrays pass through."""
        return tuple(jnp.asarray(getattr(chunk, f)) for f in fields)

    def _gather(self, adj, u, v, width):
        return gather_panels_arrays(adj.row_offsets, adj.col, adj.out_degree, u, v, width)

    def count_chunk(self, adj, chunk):
        u, v = self._upload(chunk, "u", "v")
        a, b, _, _ = self._gather(adj, u, v, chunk.width)
        return self.intersect_count(a, b)

    def per_node_chunk(self, adj, chunk, n_out):
        u, v = self._upload(chunk, "u", "v")
        a, b, _, _ = self._gather(adj, u, v, chunk.width)
        count, arm = self.intersect_per_node(a, b)
        return _panel_scatter_per_node(u, v, a, count, arm, n_out=n_out)

    def support_chunk(self, adj, chunk, m_out):
        edge_idx, u, v = self._upload(chunk, "edge_idx", "u", "v")
        a, b, _, _ = self._gather(adj, u, v, chunk.width)
        count, arm, closure = self.intersect_support(a, b)
        return support_scatter(
            edge_idx, u, v, adj.row_offsets, count, arm, closure, m_out=m_out,
        )


class PallasBackend(PanelBackend):
    """The panel plan driving the Pallas kernel family.

    Identical planning and scatters to :class:`PanelBackend`; the
    equality-tile reductions run inside
    :mod:`repro.kernels.triangle_count` (interpret mode off-TPU), with
    tile shapes steered per pow2 bucket by the optional ``tuner``.
    """

    name = "pallas"

    def _tiles(self, a, b):
        if self.tuner is None:
            return None
        return self.tuner.tiles(a.shape[0], a.shape[1], b.shape[1])

    def intersect_count(self, a, b):
        from repro.kernels.triangle_count import ops as tc_ops

        return tc_ops.intersect_count(a, b, tiles=self._tiles(a, b))

    def intersect_per_node(self, a, b):
        from repro.kernels.triangle_count import ops as tc_ops

        return tc_ops.intersect_per_node(a, b, tiles=self._tiles(a, b))

    def intersect_support(self, a, b):
        from repro.kernels.triangle_count import ops as tc_ops

        return tc_ops.intersect_support(a, b, tiles=self._tiles(a, b))


class _KeptPlans:
    """A counter's panel plans of resident graphs, each built once.

    The panel plan is a pure function of the query edges, the
    out-degrees, the width ladder and the budget.  When the workload's
    arrays are ``jax.Array``s (immutable, unlike host arrays, which can
    change in place) the plan is kept, with every chunk's ``edge_idx``,
    ``u`` and ``v`` put on the device once, and later answers on the same
    arrays take it.  An entry is keyed by the arrays' ``id`` (a
    ``jax.Array`` is not hashable) and the settings; it holds weak
    references to the arrays, confirmed on every lookup, whose callbacks
    drop the entry when an array dies, so a recycled ``id`` never finds
    a stale plan.
    """

    def __init__(self):
        self._entries: dict = {}

    def plan(self, backend: KernelBackend, work: Workload, budget: int | None):
        """``(plan, reused)``: the kept plan of ``work``, else a new one,
        kept when the backend and the arrays allow it."""
        arrays = (work.src_e, work.dst_e, work.out_degree)
        if not (isinstance(backend, PanelBackend)
                and all(isinstance(a, jax.Array) for a in arrays)):
            return backend.plan(work, budget), False
        key = (*map(id, arrays), backend.widths, budget)
        entry = self._entries.get(key)
        if entry is not None and all(r() is a for r, a in zip(entry[0], arrays)):
            kept = entry[1]
            return kept._replace(chunks=iter(kept.chunks)), True
        kept = backend.plan(work, budget)
        kept = kept._replace(chunks=tuple(
            c._replace(edge_idx=jnp.asarray(c.edge_idx), u=jnp.asarray(c.u),
                       v=jnp.asarray(c.v))
            for c in kept.chunks
        ))
        owner = weakref.ref(self)

        def drop(_dead):
            kept_plans = owner()
            if kept_plans is not None:
                kept_plans._entries.pop(key, None)

        self._entries[key] = (tuple(weakref.ref(a, drop) for a in arrays), kept)
        return kept._replace(chunks=iter(kept.chunks)), False


class DistributedBackend(KernelBackend):
    """The §III-E striped multi-device schedule — every workload.

    :meth:`plan` round-robin stripes the query edge list over every mesh
    device (edge ``i`` on stripe ``i mod S`` — the paper's
    thread-striping lifted to devices) and cuts the striped axis into
    column chunks whose *worst stripe* obeys the wedge budget
    (:func:`repro.core.distributed.plan_striped_chunks`,
    shorter-side-aware).  The chunk kernels are the ``shard_map``
    wedge kernels from :func:`repro.core.distributed.striped_workload_fn`:
    count returns per-shard segmented partials (host uint64 reduce),
    per-node merges by ``psum``, support merges arm/closure by ``psum``
    and the stripe-local base by a stripe-offset ``all_gather`` whose
    int32 payload rides a lossless delta-compressed uint16 wire when the
    graph's degree bound allows (``compress=True``, the default).

    All three are bit-identical to the wedge backend at any budget and
    any device count — the tests' simulated-mesh parity wall enforces
    this.  Results come back replicated, so the shared
    :func:`run_workload` driver accumulates them exactly like any other
    backend's.
    """

    name = "distributed"
    capabilities = frozenset(CAPABILITIES)

    def __init__(self, mesh=None, *, shorter_side: bool = False, compress: bool = True):
        self.mesh = mesh
        self.shorter_side = shorter_side
        self.compress = compress
        self.n_shards = (
            int(np.prod(mesh.devices.shape)) if mesh is not None else 0
        )
        self._adj_key = None
        self._adj_dev = None
        self._adj_bound = 0

    def _require_mesh(self):
        if self.mesh is None:
            raise ValueError(
                "the distributed backend needs a jax.sharding.Mesh; "
                "construct it via make_backend('distributed', mesh=...) or "
                "TriangleCounter(method='distributed', mesh=...)"
            )

    def plan(self, work: Workload, budget: int | None, *, bucket_pow2: bool = False) -> WorkPlan:
        from .distributed import plan_striped_chunks

        self._require_mesh()
        src, dst, deg = work.src_host, work.dst_host, work.deg_host
        m = src.shape[0]
        S = self.n_shards
        e_per = max(1, -(-m // S))
        pad = e_per * S - m
        src_p = np.concatenate([src.astype(np.int32, copy=False),
                                np.full(pad, -1, np.int32)])
        dst_p = np.concatenate([dst.astype(np.int32, copy=False),
                                np.full(pad, -1, np.int32)])
        # reshape(e_per, S).T puts edge i on stripe i % S
        src_sh = np.ascontiguousarray(src_p.reshape(e_per, S).T)
        dst_sh = np.ascontiguousarray(dst_p.reshape(e_per, S).T)
        reps = np.where(src_p >= 0, deg[np.maximum(src_p, 0)], 0).astype(np.int64)
        if self.shorter_side:
            reps_v = np.where(dst_p >= 0, deg[np.maximum(dst_p, 0)], 0).astype(np.int64)
            reps = np.minimum(reps, reps_v)
        stripe_loads = tuple(
            int(x) for x in reps.reshape(e_per, S).sum(axis=0)
        )
        bounds, eff = plan_striped_chunks(
            src_sh, deg, budget, dst_sh=dst_sh if self.shorter_side else None
        )
        cols_per_chunk = max(end - start for start, end in bounds)
        if bucket_pow2:
            eff = next_pow2(eff)
            cols_per_chunk = next_pow2(cols_per_chunk)

        def gen():
            for start, end in bounds:
                pad_c = cols_per_chunk - (end - start)
                s = src_sh[:, start:end]
                d = dst_sh[:, start:end]
                if pad_c:
                    fill = np.full((S, pad_c), -1, np.int32)
                    s = np.concatenate([s, fill], axis=1)
                    d = np.concatenate([d, fill], axis=1)
                yield StripedChunk(
                    np.ascontiguousarray(s), np.ascontiguousarray(d), start, eff
                )

        return WorkPlan(
            gen(), len(bounds), eff, int(reps.sum(dtype=np.int64)),
            n_stripes=S, stripe_loads=stripe_loads,
        )

    # -- chunk launch plumbing ---------------------------------------------

    def _device_adj(self, adj: _DeviceAdj):
        """Replicate the adjacency once per workload (cached by identity)."""
        from jax.sharding import NamedSharding, PartitionSpec

        key = (id(adj.row_offsets), id(adj.col), id(adj.out_degree))
        if self._adj_key != key:
            rep = NamedSharding(self.mesh, PartitionSpec())
            deg_np = np.asarray(adj.out_degree)
            self._adj_dev = tuple(
                jax.device_put(np.asarray(a), rep)
                for a in (adj.row_offsets, adj.col, adj.out_degree)
            )
            self._adj_bound = int(deg_np.max()) if deg_np.size else 0
            self._adj_key = key
        return self._adj_dev

    def _put_chunk(self, chunk: StripedChunk):
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(self.mesh, PartitionSpec(self.mesh.axis_names))
        return jax.device_put(chunk.src, sh), jax.device_put(chunk.dst, sh)

    def _fn(self, kind: str, adj: _DeviceAdj, chunk: StripedChunk, n_out: int):
        from repro.distributed.compression import can_narrow_int32

        from .distributed import striped_workload_fn

        narrow = (
            kind == "support" and self.compress and can_narrow_int32(self._adj_bound)
        )
        return striped_workload_fn(
            self.mesh, kind, chunk.buffer, adj.n_steps,
            n_out=n_out, shorter_side=self.shorter_side, narrow_wire=narrow,
        )

    def count_chunk(self, adj, chunk):
        self._require_mesh()
        row, col, deg = self._device_adj(adj)
        s, d = self._put_chunk(chunk)
        fn = self._fn("count", adj, chunk, 0)
        return fn(s, d, jnp.int32(chunk.start), row, col, deg)

    def per_node_chunk(self, adj, chunk, n_out):
        self._require_mesh()
        row, col, deg = self._device_adj(adj)
        s, d = self._put_chunk(chunk)
        fn = self._fn("per_node", adj, chunk, n_out)
        return fn(s, d, jnp.int32(chunk.start), row, col, deg)

    def support_chunk(self, adj, chunk, m_out):
        self._require_mesh()
        if m_out != int(adj.col.shape[0]):
            raise ValueError(
                f"distributed support needs the query list aligned with the "
                f"adjacency edge list (m_out={m_out} != |col|={int(adj.col.shape[0])})"
            )
        row, col, deg = self._device_adj(adj)
        s, d = self._put_chunk(chunk)
        fn = self._fn("support", adj, chunk, m_out)
        return fn(s, d, jnp.int32(chunk.start), row, col, deg)


_BACKEND_FACTORIES: dict[str, object] = {}


def register_backend(name: str, factory) -> None:
    """Register a backend factory under ``name``.

    The factory is called with keyword arguments
    ``factory(widths=..., tuner=..., mesh=..., shorter_side=...)`` and
    must return a :class:`KernelBackend`; accept ``**_`` for the knobs
    the backend does not use.  A registered name is directly usable as
    ``TriangleCounter(method=name)``.
    """
    _BACKEND_FACTORIES[name] = factory


register_backend("wedge_bsearch", lambda **_: WedgeBackend())
register_backend("panel", lambda widths=DEFAULT_WIDTHS, **_: PanelBackend(widths=widths))
register_backend(
    "pallas",
    lambda widths=DEFAULT_WIDTHS, tuner=None, **_: PallasBackend(
        widths=widths, tuner=tuner
    ),
)
register_backend(
    "distributed",
    lambda mesh=None, shorter_side=False, **_: DistributedBackend(
        mesh, shorter_side=shorter_side
    ),
)


def make_backend(
    name: str,
    *,
    widths=DEFAULT_WIDTHS,
    tuner=None,
    mesh=None,
    shorter_side: bool = False,
) -> KernelBackend:
    """Instantiate the backend registered under ``name``."""
    try:
        factory = _BACKEND_FACTORIES[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel backend {name!r}; registered: "
            f"{sorted(_BACKEND_FACTORIES)}"
        ) from None
    return factory(widths=widths, tuner=tuner, mesh=mesh, shorter_side=shorter_side)


_warned_fallbacks: set = set()


def resolve_backend(
    method: str,
    kind: str,
    *,
    widths=DEFAULT_WIDTHS,
    tuner=None,
    mesh=None,
    shorter_side: bool = False,
):
    """Pick the backend for (schedule, workload) by capability.

    Returns ``(backend, executed_name, fallback_reason)``.  When the
    requested backend lacks ``kind`` — or the distributed schedule is
    requested without a mesh — the wedge backend substitutes and the
    reason is returned (plus a one-time ``RuntimeWarning`` per
    (method, kind) pair per process) — capability gaps are loud.
    """
    if kind not in CAPABILITIES:
        raise ValueError(f"unknown workload kind {kind!r}; expected one of {CAPABILITIES}")
    reason = None
    if method == "distributed" and mesh is None:
        reason = (
            "backend 'distributed' needs a mesh and none was configured; "
            "fell back to 'wedge_bsearch'"
        )
    else:
        backend = make_backend(
            method, widths=widths, tuner=tuner, mesh=mesh, shorter_side=shorter_side
        )
        if kind in backend.capabilities:
            return backend, method, None
        reason = (
            f"backend {method!r} has no {kind!r} kernel; fell back to 'wedge_bsearch'"
        )
    obs.counter("engine.capability_fallbacks").add()
    key = (method, kind)
    if key not in _warned_fallbacks:
        _warned_fallbacks.add(key)
        warnings.warn(reason, RuntimeWarning, stacklevel=3)
    return make_backend("wedge_bsearch", widths=widths, tuner=tuner), "wedge_bsearch", reason


def _sanitizer():
    """The ``REPRO_CHECK=1`` runtime audit module, or None when disabled.

    Checked per call (not cached) so tests can toggle the env var; the
    import cost is one dict lookup after the first load.
    """
    flag = os.environ.get("REPRO_CHECK", "").strip().lower()
    if flag in ("", "0", "false", "off", "no"):
        return None
    from repro.check import runtime as _rt

    return _rt


def _plan_step(work: Workload, ids: dict, make):
    """An answer's planning: ``make() -> (plan, reused)`` under the
    ``engine.plan`` span (args ``edges``, ``chunks``, ``reused`` 0/1),
    counted in ``engine.plans_built`` or ``engine.plans_reused``.
    Returns ``(plan, seconds)``."""
    with obs.span("engine.plan", cat="engine", args=ids) as sp:
        plan, reused = make()
        sp.set(edges=int(work.src_host.shape[0]), chunks=plan.n_chunks, reused=int(reused))
    obs.counter("engine.plans_reused" if reused else "engine.plans_built").add()
    return plan, sp.seconds


def run_workload(
    backend: KernelBackend,
    kind: str,
    work: Workload,
    *,
    budget: int | None = None,
    n_out: int | None = None,
    bucket_pow2: bool = False,
    call: int | None = None,
    plan: WorkPlan | None = None,
):
    """Plan → launch → accumulate one workload through a backend.

    The single driver every caller shares (engine methods, analytics
    support, truss peel rounds, incremental probes).  Returns
    ``(value, plan)`` where ``value`` is the host-accumulated result —
    ``int`` for ``"count"``, int64 ``(n_out,)`` for ``"per_node"``,
    int64 per-query-edge for ``"support"`` — and ``plan`` carries the
    launch stats (``n_chunks``, ``peak_buffer``, ``total_wedges``) plus
    the phase ``timings``.

    A caller that already holds the workload's plan passes it as ``plan``
    (:class:`TriangleCounter` keeps a resident graph's panel plan, and
    plans under its own ``engine.plan`` span); the ``plan`` timing is then
    0 here.  Otherwise the backend plans the workload.

    Each phase is a :mod:`repro.obs` span, and its ``timings`` entry is
    that span's duration: ``engine.plan`` (args ``edges``, ``chunks``,
    ``reused``; see :func:`_plan_step`),
    ``engine.dispatch`` (the launch loop, one ``engine.chunk`` per chunk;
    args ``chunks``, ``h2d_bytes`` and, for panel chunks, ``slots``, and
    for support panel chunks ``scatter_lanes``, also added to the
    ``engine.scatter_lanes`` counter),
    ``engine.wait`` (one ``jax.block_until_ready``) and ``engine.fold``
    (the host copy and int64/uint64 sum of the partials; arg ``bytes``).
    A count launches every chunk, waits once and folds once; so does
    support, whose full-length int32 partials add up on the device as
    they come.  Per-node folds each chunk's full-length partial before
    the next chunk launches, so it runs the three phases once per chunk.
    ``call``, when given, tags every span with the caller's answer number.
    """
    if kind not in CAPABILITIES:
        raise ValueError(f"unknown workload kind {kind!r}")
    ids = {} if call is None else {"call": call}
    timings = {"plan": 0.0, "dispatch": 0.0, "wait": 0.0, "fold": 0.0}
    if plan is None:
        plan, timings["plan"] = _plan_step(
            work, ids, lambda: (backend.plan(work, budget, bucket_pow2=bucket_pow2), False)
        )
    # a no-op for device-resident adjacency (every engine answer)
    adj = _DeviceAdj(
        jnp.asarray(work.row_offsets), jnp.asarray(work.col),
        jnp.asarray(work.out_degree), work.n_steps,
    )
    san = _sanitizer()
    obs.counter("engine.workloads").add()
    obs.counter("engine.wedges_planned").add(plan.total_wedges)
    obs.counter("engine.chunks_launched").add(plan.n_chunks)
    obs.gauge("engine.peak_wedge_buffer").set(plan.peak_buffer)

    def launch(fn, chunk, tally, *extra, to_host=True):
        """Enqueue one chunk's copies and kernels, and count them.

        Unless ``to_host`` is false, the partial's copy to the host is
        enqueued too, so that it runs as soon as the chunk is done, under
        the later chunks' compute, and the fold finds it on the host.
        """
        with obs.span("engine.chunk", cat="engine", args={**ids, **_chunk_shape(chunk)}):
            part = fn(adj, chunk, *extra)
            if to_host and isinstance(part, jax.Array):
                part.copy_to_host_async()
        tally["chunks"] += 1
        tally["h2d_bytes"] += _upload_bytes(kind, chunk)
        if isinstance(chunk, PanelChunk):
            tally["slots"] = tally.get("slots", 0) + 2 * len(chunk.u) * chunk.width
            if kind == "support":
                # support_scatter's adds: base, wu arm and wv closure lanes a row
                lanes = len(chunk.u) * (1 + 2 * chunk.width)
                tally["scatter_lanes"] = tally.get("scatter_lanes", 0) + lanes
                obs.counter("engine.scatter_lanes").add(lanes)
        return part

    def done(value):
        return value, plan._replace(timings=timings)

    if kind == "count":
        tally = {"chunks": 0, "h2d_bytes": 0}
        with obs.span("engine.dispatch", cat="engine", args=ids) as sp:
            partials = [launch(backend.count_chunk, chunk, tally) for chunk in plan.chunks]
            sp.set(**tally)
        timings["dispatch"] = sp.seconds
        with obs.span("engine.wait", cat="engine", args=ids) as sp:
            jax.block_until_ready(partials)
        timings["wait"] = sp.seconds
        with obs.span("engine.fold", cat="engine", args=ids) as sp:
            if san is not None:
                san.check_partials(partials, kind="count")
            total = accumulate_partials(partials)
            sp.set(bytes=sum(int(p.nbytes) for p in partials))
        timings["fold"] = sp.seconds
        return done(total)

    if kind == "support":
        # an edge's support is below the vertex count, so the chunks'
        # int32 partials add up on the device: one wait and one fold
        m = int(work.src_host.shape[0])
        tally = {"chunks": 0, "h2d_bytes": 0}
        total = jnp.zeros((m,), jnp.int32)
        with obs.span("engine.dispatch", cat="engine", args=ids) as sp:
            for chunk in plan.chunks:
                part = launch(backend.support_chunk, chunk, tally, m, to_host=False)
                total = _add_partial(total, part)
            sp.set(**tally)
        timings["dispatch"] = sp.seconds
        with obs.span("engine.wait", cat="engine", args=ids) as sp:
            jax.block_until_ready(total)
        timings["wait"] = sp.seconds
        with obs.span("engine.fold", cat="engine", args=ids) as sp:
            if san is not None:
                san.check_partial(total, kind=kind, context="sum of the chunks")
            out = np.asarray(total, dtype=np.int64)
            sp.set(bytes=int(total.nbytes))
        timings["fold"] = sp.seconds
        return done(out)

    m = adj.row_offsets.shape[0] - 1 if n_out is None else n_out
    out = np.zeros((m,), np.int64)
    for i, chunk in enumerate(plan.chunks):
        tally = {"chunks": 0, "h2d_bytes": 0}
        with obs.span("engine.dispatch", cat="engine", args=ids) as sp:
            part = launch(backend.per_node_chunk, chunk, tally, m)
            sp.set(**tally)
        timings["dispatch"] += sp.seconds
        with obs.span("engine.wait", cat="engine", args=ids) as sp:
            jax.block_until_ready(part)
        timings["wait"] += sp.seconds
        with obs.span("engine.fold", cat="engine", args=ids) as sp:
            if san is not None:
                san.check_partial(part, kind=kind, context=f"chunk {i}")
            out += np.asarray(part, dtype=np.int64)
            sp.set(bytes=int(part.nbytes))
        timings["fold"] += sp.seconds
    return done(out)


@jax.jit
def _add_partial(total, part):
    return total + part


def _chunk_shape(chunk) -> dict:
    """An ``engine.chunk`` span's args: a panel chunk's ``width`` and
    ``rows`` (padded rows included), else the launch's wedge ``buffer``."""
    if isinstance(chunk, PanelChunk):
        return {"width": chunk.width, "rows": len(chunk.u)}
    return {"buffer": int(getattr(chunk, "buffer", 0))}


def _upload_bytes(kind: str, chunk) -> int:
    """Bytes of the chunk's host index arrays its launch copies to the
    device: ``u``/``v`` of a panel chunk (and ``edge_idx`` for support),
    ``src``/``dst`` of a wedge or striped chunk held on the host."""
    if isinstance(chunk, PanelChunk):
        arrays = (chunk.u, chunk.v) + ((chunk.edge_idx,) if kind == "support" else ())
    else:
        arrays = (getattr(chunk, "src", None), getattr(chunk, "dst", None))
    return sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))


def iter_wedge_chunks(csr: OrientedCSR, max_wedge_chunk: int | None, *, bucket_pow2: bool = False):
    """Lazily yield −1-padded fixed-shape ``(src, dst, start)`` chunks.

    The historical edge-chunk iterator, now a thin view over
    :meth:`WedgeBackend.plan`.  ``start`` is each chunk's offset into the
    directed edge list — add it to a kernel's local edge ids to recover
    global edge indices (the per-edge support scatter needs this).
    ``csr.src``/``csr.col`` may carry a −1-padded tail (padded slots
    contribute no wedges), and ``bucket_pow2`` rounds the chunk width and
    the peak buffer up to powers of two — together these let
    shape-churning callers (the truss peel's shrinking subgraphs) reuse
    O(log m) kernel compilations.

    Returns ``(generator, n_chunks, peak, total_wedges)`` where ``peak``
    is the per-launch buffer: the largest chunk's wedge load (pow2-rounded
    when bucketing), which the kernels materialize exactly — it can
    undercut the planner's effective budget when no greedy chunk fills
    it.  Only one padded chunk copy is resident at a time, so host
    overhead stays O(chunk) in the larger-than-memory regime the budget
    targets.
    """
    plan = WedgeBackend().plan(
        workload_from_csr(csr), max_wedge_chunk, bucket_pow2=bucket_pow2
    )
    gen = ((c.src, c.dst, c.start) for c in plan.chunks)
    return gen, plan.n_chunks, plan.peak_buffer, plan.total_wedges


# ---------------------------------------------------------------------------
# auto dispatch
# ---------------------------------------------------------------------------


def choose_method(
    *,
    max_out_degree: int,
    mean_out_degree: float,
    mesh=None,
    widths: tuple[int, ...] = DEFAULT_WIDTHS,
    backend: str | None = None,
) -> str:
    """Pick a counting schedule from graph statistics (§III-C skew logic).

    * a multi-device mesh always wins — the §III-E striping scales and is
      exact regardless of skew;
    * on TPU, panels that fit the largest bucket go to the Pallas kernel
      (equality tiles keep the VPU lanes busy; the texture-cache role is
      played by explicit VMEM staging) — it compiles natively at every
      bucket width and its ×4 extensions, which ``tests/test_tpu_compile.py``
      checks against a described v5e chip;
    * low degree + low skew favors the jnp panel schedule (padding waste
      bounded, O(L²) constant small);
    * heavy tails — Kronecker-style skew — favor ``wedge_bsearch``, whose
      log-factor cost is immune to padding waste.
    """
    if mesh is not None and int(np.prod(mesh.devices.shape)) > 1:
        return "distributed"
    backend = backend or jax.default_backend()
    skew = max_out_degree / max(mean_out_degree, 1e-9)
    if backend == "tpu" and max_out_degree <= widths[-1]:
        return "pallas"
    if max_out_degree <= 64 and skew <= 16.0:
        return "panel"
    return "wedge_bsearch"


def resolve_method(method: str, out_degree, *, mesh=None, widths=DEFAULT_WIDTHS) -> str:
    """Resolve ``"auto"`` against an out-degree histogram (never "auto")."""
    if method != "auto":
        return method
    out_deg = np.asarray(out_degree)
    max_deg = int(out_deg.max()) if out_deg.size else 0
    mean_deg = float(out_deg.mean()) if out_deg.size else 0.0
    return choose_method(
        max_out_degree=max_deg, mean_out_degree=mean_deg, mesh=mesh, widths=widths
    )


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


_ANSWERS = itertools.count()


def _answer_id() -> dict:
    """``{"call": n}``: the process's answer number, which tags every
    span of one engine answer."""
    return {"call": next(_ANSWERS)}


class TriangleCounter:
    """Unified, memory-bounded triangle counting over every schedule.

    Parameters
    ----------
    method:
        One of ``"auto"``, ``"wedge_bsearch"``, ``"panel"``, ``"pallas"``,
        ``"distributed"``.
    max_wedge_chunk:
        Wedge-buffer budget per device launch (slots).  ``None`` runs a
        single full-size launch.
    widths:
        Panel bucket boundaries for the panel/Pallas schedules.
    mesh:
        ``jax.sharding.Mesh`` for the distributed schedule (required when
        ``method="distributed"``; enables it under ``"auto"``).
    shorter_side:
        Distributed only — enumerate wedge candidates from the smaller
        endpoint list (§Perf "opt" variant in EXPERIMENTS.md).
    tuner:
        Optional :class:`repro.core.tuning.AutoTuner` steering the Pallas
        kernels' tile shapes from its on-disk grid-search cache.

    After any call, :attr:`last_stats` holds an :class:`EngineStats`
    describing what ran (resolved method, executed method, chunk count,
    peak buffer, and any capability-fallback reason).

    A panel or Pallas plan of a resident graph (an :class:`OrientedCSR`
    of ``jax.Array``s) is built by the first answer on it and kept, with
    its chunk index arrays on the device, for every later answer of any
    kind on the same arrays (:class:`_KeptPlans`); it is dropped when
    the arrays die.
    """

    def __init__(
        self,
        method: str = "auto",
        max_wedge_chunk: int | None = None,
        widths: tuple[int, ...] = DEFAULT_WIDTHS,
        mesh=None,
        shorter_side: bool = False,
        tuner=None,
    ):
        if method not in METHODS and method not in _BACKEND_FACTORIES:
            raise ValueError(
                f"unknown method {method!r}; expected one of {METHODS} "
                f"or a registered backend ({sorted(_BACKEND_FACTORIES)})"
            )
        if method == "distributed" and mesh is None:
            raise ValueError("method='distributed' requires a mesh")
        if max_wedge_chunk is not None and max_wedge_chunk < 1:
            raise ValueError("max_wedge_chunk must be positive")
        self.method = method
        self.max_wedge_chunk = max_wedge_chunk
        self.widths = tuple(widths)
        self.mesh = mesh
        self.shorter_side = shorter_side
        self.tuner = tuner
        self.last_stats: EngineStats | None = None
        self._plans = _KeptPlans()

    # -- public API ---------------------------------------------------------

    def count(self, edges, n_nodes: int | None = None) -> int:
        """Exact global triangle count.

        ``edges`` may be a canonical edge array, a pre-built
        :class:`OrientedCSR` (preprocessing skipped entirely), or a cached
        undirected CSR (anything with ``row_offsets``/``col``/``n_nodes``,
        e.g. ``repro.graphs.io.CSRGraph`` loaded from a ``.tricsr`` file —
        oriented by a host-side filter, never re-canonicalized).
        """
        self.last_stats = None
        with obs.span("engine.count", cat="engine", args=_answer_id()) as sp:
            csr, prep_s = self._prepare_timed(edges, n_nodes, sp.args)
            if csr is None:
                return 0
            return self._run(csr, "count", prep_s, sp.args)

    def per_node(self, edges, n_nodes: int | None = None) -> np.ndarray:
        """Per-vertex triangle incidences, int64 host array.

        Runs whichever backend the configured/dispatched schedule
        registers — the panel and Pallas backends scatter their arm
        attributions natively, and the distributed backend psum-merges
        per-stripe scatters — so ``method="pallas"`` genuinely executes
        the Pallas kernels here and ``method="distributed"`` genuinely
        executes on every mesh device.
        """
        self.last_stats = None
        with obs.span("engine.per_node", cat="engine", args=_answer_id()) as sp:
            csr, prep_s = self._prepare_timed(edges, n_nodes, sp.args)
            if csr is None:
                n = n_nodes if n_nodes is not None else getattr(edges, "n_nodes", 0) or 0
                return np.zeros((n,), np.int64)
            return self._run(csr, "per_node", prep_s, sp.args)

    def edge_support(self, edges, n_nodes: int | None = None) -> np.ndarray:
        """Per-directed-edge triangle support, int64 host array.

        Aligned with the oriented CSR's ``(src, col)`` edge list; the sum
        is exactly ``3 × count``.  The richer dataclass wrapper (top-k,
        totals) lives in :func:`repro.analytics.support.edge_support`,
        which routes through this method.
        """
        self.last_stats = None
        with obs.span("engine.support", cat="engine", args=_answer_id()) as sp:
            csr, prep_s = self._prepare_timed(edges, n_nodes, sp.args)
            if csr is None:
                return np.zeros((0,), np.int64)
            return self._run(csr, "support", prep_s, sp.args)

    def per_node_counts(self, edges, n_nodes: int | None = None) -> np.ndarray:
        """Alias of :meth:`per_node` (clearer name for analytics callers)."""
        return self.per_node(edges, n_nodes)

    @staticmethod
    def _degree_hist(edges, n_nodes: int | None):
        """Undirected degrees + node count for any accepted input kind."""
        return degree_histogram(edges, n_nodes)

    def clustering(self, edges, n_nodes: int | None = None) -> np.ndarray:
        """Local clustering coefficients c(v) = 2·T(v) / (deg(v)·(deg(v)−1))."""
        from .clustering import clustering_from_counts

        deg, n_nodes = self._degree_hist(edges, n_nodes)
        if deg.size == 0:
            return np.zeros((n_nodes,), np.float64)
        tri = self.per_node(edges, n_nodes)
        return clustering_from_counts(tri, deg)

    def transitivity(self, edges, n_nodes: int | None = None) -> float:
        """Global transitivity ratio 3·#triangles / #wedges."""
        from .clustering import transitivity_from_counts

        deg, n_nodes = self._degree_hist(edges, n_nodes)
        if deg.size == 0:
            return 0.0
        t = self.count(edges, n_nodes)
        return transitivity_from_counts(t, deg)

    # -- shared plumbing ----------------------------------------------------

    def _prepare_timed(self, edges, n_nodes: int | None, ids: dict):
        """``(_prepare result, preprocess seconds)`` under a span."""
        with obs.span("engine.preprocess", cat="engine", args=ids) as sp:
            csr = self._prepare(edges, n_nodes)
        return csr, sp.seconds

    def _prepare(self, edges, n_nodes: int | None) -> OrientedCSR | None:
        csr = prepare_oriented(edges, n_nodes)
        if csr is not None:
            return csr
        # empty graph: no CSR to resolve "auto" against; record the
        # trivial schedule
        resolved = self.method if self.method != "auto" else "wedge_bsearch"
        self.last_stats = EngineStats(
            method=resolved, resolved_method=resolved, n_chunks=0,
            peak_wedge_buffer=0, wedge_budget=self.max_wedge_chunk,
            total_wedges=0, n_directed_edges=0,
        )
        return None

    def _resolve(self, csr: OrientedCSR) -> str:
        return resolve_method(
            self.method, csr.out_degree, mesh=self.mesh, widths=self.widths
        )

    @staticmethod
    def _search_steps(csr: OrientedCSR) -> int:
        return search_steps(csr)

    def _record(self, method, n_chunks, peak, total_wedges, m_dir,
                resolved=None, fallback_reason=None, stripe_loads=None,
                n_stripes=1, timings=None):
        skew = straggler = None
        if stripe_loads is not None:
            from repro.distributed.straggler import stripe_skew_report

            load_rep = stripe_skew_report(stripe_loads)
            skew = load_rep.skew
            straggler = load_rep.straggler_stripe
        self.last_stats = EngineStats(
            method=method,
            resolved_method=resolved or method,
            n_chunks=n_chunks,
            peak_wedge_buffer=peak,
            wedge_budget=self.max_wedge_chunk,
            total_wedges=total_wedges,
            n_directed_edges=m_dir,
            fallback_reason=fallback_reason,
            n_stripes=n_stripes,
            stripe_skew=skew,
            straggler_stripe=straggler,
            timings=timings,
        )

    def _run(self, csr: OrientedCSR, kind: str, prep_s: float, ids: dict):
        """Dispatch one workload through the capability-resolved backend.

        ``engine.host_copy`` covers every read of the resident graph to
        the host before planning (``auto`` resolution, the search depth,
        the workload's host views); its ``bytes`` are those of the device
        arrays read.  JAX keeps an array's host copy once made, so only
        the first answer on a resident graph actually copies them.
        """
        with obs.span("engine.host_copy", cat="engine", args=ids) as sp:
            resolved = self._resolve(csr)
            work = workload_from_csr(csr)
            sp.set(bytes=sum(
                int(a.nbytes) for a in (csr.src, csr.col, csr.out_degree)
                if isinstance(a, jax.Array)
            ))
        host_copy_s = sp.seconds
        backend, executed, reason = resolve_backend(
            resolved, kind, widths=self.widths, tuner=self.tuner,
            mesh=self.mesh, shorter_side=self.shorter_side,
        )
        plan, plan_s = _plan_step(
            work, ids, lambda: self._plans.plan(backend, work, self.max_wedge_chunk)
        )
        value, plan = run_workload(
            backend, kind, work,
            budget=self.max_wedge_chunk,
            n_out=csr.n_nodes if kind == "per_node" else None,
            call=ids["call"],
            plan=plan,
        )
        self._record(
            executed, plan.n_chunks, plan.peak_buffer, plan.total_wedges,
            csr.n_directed_edges, resolved=resolved, fallback_reason=reason,
            stripe_loads=plan.stripe_loads, n_stripes=plan.n_stripes,
            timings={"preprocess": prep_s, "host_copy": host_copy_s,
                     **plan.timings, "plan": plan_s},
        )
        return value
