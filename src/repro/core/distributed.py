"""Distributed triangle counting (paper §III-E scaled to a 512-chip mesh).

The paper's multi-GPU scheme: preprocess once, replicate the CSR arrays to
every device, partition the *edge list*, reduce partial counts.  We keep
that exact structure under ``shard_map``:

* the oriented CSR (``row_offsets``, ``col``, ``out_degree``) is replicated
  (it is the read-only "texture" data of the kernel),
* the directed edge list is **striped round-robin** across every mesh axis
  — the same modulo-striping the paper uses to assign edges to threads
  (§III-C), which statistically balances the wedge workload under skewed
  degree distributions,
* each shard expands its edges into wedge candidates and closes them with
  the batched binary search from :mod:`repro.core.count`,
* partial counts meet in a single ``psum`` (the paper's final
  ``thrust::reduce``).

The counting step is Amdahl-free; preprocessing is replicated (as in the
paper, where it runs on one GPU).  §Perf in EXPERIMENTS.md quantifies the
preprocessing fraction exactly as the paper's §III-E does.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .count import _batched_contains, _batched_search, segmented_int32_sum
from .preprocess import OrientedCSR, preprocess
from repro.distributed.compression import ensure_fits_int32

__all__ = [
    "stripe_edges",
    "plan_striped_chunks",
    "make_distributed_count_fn",
    "make_distributed_panel_count_fn",
    "striped_workload_fn",
    "count_triangles_distributed",
    "count_triangles_distributed_csr",
    "count_triangles_distributed_slabs",
    "count_triangles_distributed_panel",
    "oriented_csr_from_slabs",
]

def stripe_edges(csr: OrientedCSR, n_shards: int, shorter_side: bool = False):
    """Round-robin stripe directed edges into ``(n_shards, e_per_shard)``.

    Shard ``s`` receives directed edges ``s, s + S, s + 2S, …`` (−1 padded),
    mirroring the paper's thread-striping.  Returns host arrays
    ``(src_sh, dst_sh, wedges_per_shard_max)``.

    ``shorter_side`` sizes the wedge buffer for the §Perf variant that
    enumerates candidates from the *smaller* endpoint list.
    """
    src = np.asarray(csr.src)
    dst = np.asarray(csr.col)
    out_deg = np.asarray(csr.out_degree)
    m = src.shape[0]
    e_per = -(-m // n_shards)
    pad = e_per * n_shards - m
    src_p = np.concatenate([src, np.full(pad, -1, np.int32)])
    dst_p = np.concatenate([dst, np.full(pad, -1, np.int32)])
    # reshape(e_per, S).T puts edge i on shard i % S — round-robin striping
    src_sh = np.ascontiguousarray(src_p.reshape(e_per, n_shards).T)
    dst_sh = np.ascontiguousarray(dst_p.reshape(e_per, n_shards).T)
    reps = np.where(src_p >= 0, out_deg[np.maximum(src_p, 0)], 0)
    if shorter_side:
        reps_v = np.where(dst_p >= 0, out_deg[np.maximum(dst_p, 0)], 0)
        reps = np.minimum(reps, reps_v)
    w_per_shard = reps.reshape(e_per, n_shards).sum(axis=0)
    return src_sh, dst_sh, int(w_per_shard.max()) if m else 1


def plan_striped_chunks(
    src_sh: np.ndarray,
    out_deg: np.ndarray,
    budget: int | None,
    dst_sh: np.ndarray | None = None,
):
    """Partition the striped per-shard edge axis under a wedge budget.

    ``src_sh`` is the ``(n_shards, e_per)`` striped source array from
    :func:`stripe_edges` (−1 padded).  Returns ``(bounds, eff)`` where
    each column slice ``[start, end)`` in ``bounds`` keeps *every*
    shard's wedge-buffer requirement ≤ ``eff``, and
    ``eff = max(budget, max single-edge fan-out)`` (a chunk must hold at
    least one whole edge per shard).  With ``budget=None`` the whole axis
    is one chunk sized to the worst shard — the unchunked behavior.

    Pass ``dst_sh`` for the shorter-side variant: fan-outs are then
    ``min(deg⁺(u), deg⁺(v))``, matching what the kernel enumerates, so
    the budget is not over-reserved from the src side alone.
    """
    out_deg = np.asarray(out_deg)
    reps = np.where(src_sh >= 0, out_deg[np.maximum(src_sh, 0)], 0).astype(np.int64)
    if dst_sh is not None:
        reps_v = np.where(dst_sh >= 0, out_deg[np.maximum(dst_sh, 0)], 0).astype(np.int64)
        reps = np.minimum(reps, reps_v)
    e_per = src_sh.shape[1]
    per_shard_total = reps.sum(axis=1)
    if e_per == 0:
        return [(0, 0)], 1
    if budget is None or budget >= int(per_shard_total.max()):
        return [(0, e_per)], max(int(per_shard_total.max()), 1)
    eff = max(int(budget), int(reps.max()), 1)
    cum = np.cumsum(reps, axis=1)  # (S, e_per) per-shard running wedge load
    bounds = []
    start = 0
    while start < e_per:
        base = cum[:, start - 1] if start else np.zeros(cum.shape[0], np.int64)
        # furthest end each shard tolerates; the chunk ends at the minimum
        ends = np.array(
            [np.searchsorted(cum[s], base[s] + eff, side="right") for s in range(cum.shape[0])]
        )
        end = max(int(ends.min()), start + 1)
        bounds.append((start, end))
        start = end
    return bounds, eff


def make_distributed_count_fn(
    mesh: Mesh,
    wedge_budget: int,
    n_search_steps: int,
    axis_names: Sequence[str] | None = None,
    shorter_side: bool = False,
):
    """Build the jitted sharded counting step.

    ``wedge_budget`` is the per-shard wedge-buffer length (static), computed
    by :func:`stripe_edges`; ``n_search_steps`` bounds the binary search.
    Edge shards live on the product of every mesh axis; the CSR is
    replicated.  Returns ``f(src_sh, dst_sh, row_offsets, col, out_degree)
    -> per-shard partial counts, (n_shards, n_segments) int32`` where each
    partial covers one 2²⁰-slot segment of the shard's wedge buffer — a
    segment sum never exceeds 2²⁰, so int32 stays safe even when a shard
    closes ≥ 2³¹ wedges in one launch; callers reduce in uint64 on host.

    ``shorter_side`` (§Perf): enumerate wedge candidates from the *smaller*
    of N⁺(u), N⁺(v) and binary-search the larger — |N⁺(u) ∩ N⁺(v)| is
    symmetric, so the count is identical while the probe count drops from
    Σ deg⁺(u) to Σ min(deg⁺(u), deg⁺(v)).
    """
    axes = tuple(axis_names or mesh.axis_names)

    def shard_body(src_e, dst_e, row_offsets, col, out_deg):
        src_e = src_e.reshape(-1)
        dst_e = dst_e.reshape(-1)
        m_local = src_e.shape[0]
        valid_e = src_e >= 0
        safe_src = jnp.maximum(src_e, 0)
        safe_dst = jnp.maximum(dst_e, 0)
        if shorter_side:
            du = out_deg[safe_src]
            dv = out_deg[safe_dst]
            swap = dv < du
            enum_v = jnp.where(swap, safe_dst, safe_src)   # enumerate this list
            probe_v = jnp.where(swap, safe_src, safe_dst)  # search in this one
            reps = jnp.where(valid_e, jnp.minimum(du, dv), 0)
        else:
            enum_v = safe_src
            probe_v = safe_dst
            reps = jnp.where(valid_e, out_deg[safe_src], 0)
        starts = jnp.cumsum(reps) - reps
        edge_id = jnp.repeat(
            jnp.arange(m_local, dtype=jnp.int32),
            reps,
            total_repeat_length=wedge_budget,
        )
        pos = jnp.arange(wedge_budget, dtype=jnp.int32) - starts[edge_id]
        valid = (pos >= 0) & (pos < reps[edge_id])
        u = enum_v[edge_id]
        v = probe_v[edge_id]
        w_idx = jnp.clip(row_offsets[u] + pos, 0, col.shape[0] - 1)
        w = col[w_idx]
        found = _batched_contains(
            col, row_offsets[v], row_offsets[v + 1], w, n_search_steps
        )
        partial = segmented_int32_sum(found & valid)
        return partial.reshape((1,) * len(axes) + (-1,))

    edge_spec = P(axes)  # edge-shard dim split over the flattened mesh
    rep = P()
    f = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(edge_spec, edge_spec, rep, rep, rep),
        out_specs=P(*axes, None),
    )
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def striped_workload_fn(
    mesh: Mesh,
    kind: str,
    wedge_budget: int,
    n_search_steps: int,
    n_out: int = 0,
    shorter_side: bool = False,
    narrow_wire: bool = False,
):
    """Build (and cache) the jitted striped kernel for one workload kind.

    The §III-E scheme generalized beyond the scalar count: every shard
    expands/closes wedges for its round-robin edge stripe, then the
    partials merge by the collective each workload needs —

    ``"count"``
        per-shard segmented int32 partials, no collective (the host
        reduces in uint64, as in :func:`make_distributed_count_fn`);
    ``"per_node"``
        each shard scatters its hits to the triangle's three vertices in
        a local ``(n_out,)`` array and the shards ``psum`` — the output
        is the replicated exact per-node incidence of the chunk;
    ``"support"``
        two merges.  Arm ``(u, w)`` and closure ``(v, w)`` contributions
        land on *global* directed-edge (``col``) indices, so they psum
        like per-node.  The base ``(u, v)`` contribution belongs to the
        shard's own stripe: each shard reduces it per local edge column,
        the ``(cols,)`` vectors ride a delta-compressed ``all_gather``
        (:func:`repro.distributed.compression.compressed_all_gather_int32`,
        uint16 wire when ``narrow_wire``), and the gathered ``(S, cols)``
        block scatters onto stable global edge ids
        ``(chunk_start + c)·S + s`` — the inverse of the round-robin
        striping, independent of which device computed what.

    Signature of the returned jitted fn::

        f(src_sh, dst_sh, chunk_start, row_offsets, col, out_degree)

    with ``src_sh``/``dst_sh`` the −1-padded ``(S, cols)`` striped chunk
    (sharded over every mesh axis), ``chunk_start`` a traced int32 column
    offset (no recompile per chunk) and the CSR replicated.  Results are
    bit-identical to the single-device kernels: same wedge enumeration,
    same closure, integer scatters are order-free.

    Cached by ``functools.lru_cache`` so shape-stable callers (the truss
    peel's pow2-bucketed rounds, the incremental probes) reuse one
    compiled kernel per (kind, budget, steps, n_out) across backend
    instances — compiles stay O(log m) per decomposition.
    """
    if kind not in ("count", "per_node", "support"):
        raise ValueError(f"unknown striped workload kind {kind!r}")
    from repro.distributed.compression import compressed_all_gather_int32

    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod(mesh.devices.shape))

    def shard_body(src_e, dst_e, chunk_start, row_offsets, col, out_deg):
        src_e = src_e.reshape(-1)
        dst_e = dst_e.reshape(-1)
        cols = src_e.shape[0]
        valid_e = src_e >= 0
        safe_src = jnp.maximum(src_e, 0)
        safe_dst = jnp.maximum(dst_e, 0)
        if shorter_side:
            du = out_deg[safe_src]
            dv = out_deg[safe_dst]
            swap = dv < du
            enum_v = jnp.where(swap, safe_dst, safe_src)
            probe_v = jnp.where(swap, safe_src, safe_dst)
            reps = jnp.where(valid_e, jnp.minimum(du, dv), 0)
        else:
            enum_v = safe_src
            probe_v = safe_dst
            reps = jnp.where(valid_e, out_deg[safe_src], 0)
        starts = jnp.cumsum(reps) - reps
        edge_id = jnp.repeat(
            jnp.arange(cols, dtype=jnp.int32), reps,
            total_repeat_length=wedge_budget,
        )
        pos = jnp.arange(wedge_budget, dtype=jnp.int32) - starts[edge_id]
        valid = (pos >= 0) & (pos < reps[edge_id])
        u = enum_v[edge_id]
        v = probe_v[edge_id]
        w_idx = jnp.clip(row_offsets[u] + pos, 0, col.shape[0] - 1)
        w = col[w_idx]
        found, vw_idx = _batched_search(
            col, row_offsets[v], row_offsets[v + 1], w, n_search_steps
        )
        hit = found & valid
        if kind == "count":
            partial = segmented_int32_sum(hit)
            return partial.reshape((1,) * len(axes) + (-1,))
        inc = hit.astype(jnp.int32)
        if kind == "per_node":
            # w may read a padded/sentinel col slot on non-hit lanes; its
            # inc is 0 and out-of-range scatter indices drop under jit
            out = jnp.zeros((n_out,), jnp.int32)
            out = out.at[u].add(inc, mode="drop")
            out = out.at[v].add(inc, mode="drop")
            out = out.at[w].add(inc, mode="drop")
            return jax.lax.psum(out, axes)
        # support: arm/closure hit global col indices — psum them; the
        # base contribution stays stripe-local until the all_gather
        ac = jnp.zeros((n_out,), jnp.int32)
        ac = ac.at[w_idx].add(inc, mode="drop")
        ac = ac.at[vw_idx].add(inc, mode="drop")
        ac = jax.lax.psum(ac, axes)
        base = jnp.zeros((cols,), jnp.int32).at[edge_id].add(inc, mode="drop")
        base_all = compressed_all_gather_int32(base, axes, narrow=narrow_wire)
        # stripe-offset scatter: column c of gathered stripe s is global
        # query edge (chunk_start + c)·S + s (inverse round-robin); padded
        # tail ids land past n_out with zero base and drop
        c = jnp.arange(cols, dtype=jnp.int32)
        s = jnp.arange(n_shards, dtype=jnp.int32)
        gid = (chunk_start + c)[None, :] * n_shards + s[:, None]
        return ac.at[gid.reshape(-1)].add(base_all.reshape(-1), mode="drop")

    edge_spec = P(axes)
    rep = P()
    out_spec = P(*axes, None) if kind == "count" else P()
    f = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(edge_spec, edge_spec, rep, rep, rep, rep),
        out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(f)


def make_distributed_panel_count_fn(
    mesh: Mesh,
    edges_per_shard_by_width: dict[int, int],
    axis_names: Sequence[str] | None = None,
):
    """§Perf: distributed *panel* schedule — the Pallas kernel's dataflow.

    Instead of ``log₂(deg_max)`` random gathers per wedge probe, each edge
    streams both endpoint neighbor panels exactly once and closes the
    intersection with an equality-tile reduction (compares stay in
    registers/VMEM).  Edges are bucketed by panel width; the per-shard
    bucket sizes are static.  Takes per-width striped ``(n_shards, e_w)``
    src/dst arrays + the replicated CSR; returns per-shard int32 partials.
    """
    axes = tuple(axis_names or mesh.axis_names)
    widths = sorted(edges_per_shard_by_width)

    def shard_body(*args):
        n_w = len(widths)
        srcs = args[:n_w]
        dsts = args[n_w : 2 * n_w]
        row_offsets, col, out_deg = args[2 * n_w :]
        total = jnp.int32(0)
        m_dir = col.shape[0]
        for width, src_e, dst_e in zip(widths, srcs, dsts):
            src_e = src_e.reshape(-1)
            dst_e = dst_e.reshape(-1)
            valid_e = src_e >= 0
            u = jnp.maximum(src_e, 0)
            v = jnp.maximum(dst_e, 0)
            lane = jnp.arange(width, dtype=jnp.int32)

            def panel(base, length):
                idx = jnp.clip(base[:, None] + lane[None, :], 0, m_dir - 1)
                vals = col[idx]
                return jnp.where(lane[None, :] < length[:, None], vals, -1)

            a = panel(row_offsets[u], out_deg[u])   # (E_w, width)
            b = panel(row_offsets[v], out_deg[v])
            eq = (a[:, :, None] == b[:, None, :]) & (a[:, :, None] >= 0)
            counts = jnp.sum(eq, axis=(1, 2), dtype=jnp.int32)
            total = total + jnp.sum(
                jnp.where(valid_e, counts, 0), dtype=jnp.int32
            )
        return total.reshape((1,) * len(axes))

    edge_spec = P(axes)
    rep = P()
    in_specs = tuple([edge_spec] * (2 * len(widths)) + [rep, rep, rep])
    f = shard_map(shard_body, mesh=mesh, in_specs=in_specs, out_specs=P(*axes))
    return jax.jit(f), widths


def count_triangles_distributed_csr(
    csr: OrientedCSR,
    mesh: Mesh,
    shorter_side: bool = False,
    max_wedge_chunk: int | None = None,
    stats_out: dict | None = None,
) -> int:
    """Sharded count from a prebuilt CSR (stripe → chunk → sharded count).

    ``max_wedge_chunk`` bounds every shard's wedge buffer: the striped
    edge axis is sliced into column chunks (:func:`plan_striped_chunks`),
    each padded to a fixed width so one jitted ``shard_map`` kernel is
    reused across chunks.  This is the engine's memory-bounded
    partitioning composed with the paper's §III-E striping.  Partial
    counts accumulate on host in uint64.
    """
    n_shards = int(np.prod(mesh.devices.shape))
    src_sh, dst_sh, _ = stripe_edges(csr, n_shards, shorter_side=shorter_side)
    max_deg = int(np.asarray(csr.out_degree).max()) if csr.n_nodes else 0
    steps = max(1, int(np.ceil(np.log2(max_deg + 1)))) if max_deg else 1
    bounds, eff = plan_striped_chunks(
        src_sh,
        np.asarray(csr.out_degree),
        max_wedge_chunk,
        dst_sh=dst_sh if shorter_side else None,
    )
    cols_per_chunk = max(end - start for start, end in bounds)
    count_fn = make_distributed_count_fn(mesh, eff, steps, shorter_side=shorter_side)
    rep_sharding = NamedSharding(mesh, P())
    edge_sharding = NamedSharding(mesh, P(mesh.axis_names))
    csr_dev = (
        jax.device_put(np.asarray(csr.row_offsets), rep_sharding),
        jax.device_put(np.asarray(csr.col), rep_sharding),
        jax.device_put(np.asarray(csr.out_degree), rep_sharding),
    )
    total = np.uint64(0)
    for start, end in bounds:
        pad = cols_per_chunk - (end - start)
        s = src_sh[:, start:end]
        d = dst_sh[:, start:end]
        if pad:
            fill = np.full((n_shards, pad), -1, np.int32)
            s = np.concatenate([s, fill], axis=1)
            d = np.concatenate([d, fill], axis=1)
        partials = count_fn(
            jax.device_put(np.ascontiguousarray(s), edge_sharding),
            jax.device_put(np.ascontiguousarray(d), edge_sharding),
            *csr_dev,
        )
        total += np.uint64(np.asarray(partials).astype(np.uint64).sum())
    if stats_out is not None:
        stats_out["n_chunks"] = len(bounds)
        stats_out["peak_wedge_buffer"] = eff
        stats_out["cols_per_chunk"] = cols_per_chunk
    return int(total)


def count_triangles_distributed(
    edges,
    mesh: Mesh,
    n_nodes: int | None = None,
    shorter_side: bool = False,
    max_wedge_chunk: int | None = None,
) -> int:
    """End-to-end distributed count (preprocess → stripe → sharded count)."""
    edges = np.asarray(edges)
    if edges.size == 0:
        return 0
    if n_nodes is None:
        n_nodes = int(edges.max()) + 1
    csr = preprocess(jnp.asarray(edges), n_nodes=n_nodes)
    return count_triangles_distributed_csr(
        csr, mesh, shorter_side=shorter_side, max_wedge_chunk=max_wedge_chunk
    )


def count_triangles_distributed_panel(
    edges,
    mesh: Mesh,
    n_nodes: int | None = None,
    widths: tuple[int, ...] = (16, 64, 256, 1024, 4096, 16384),
) -> int:
    """End-to-end distributed count via the panel (Pallas-dataflow) schedule."""
    edges = np.asarray(edges)
    if edges.size == 0:
        return 0
    if n_nodes is None:
        n_nodes = int(edges.max()) + 1
    csr = preprocess(jnp.asarray(edges), n_nodes=n_nodes)
    n_shards = int(np.prod(mesh.devices.shape))
    src = np.asarray(csr.src)
    dst = np.asarray(csr.col)
    out_deg = np.asarray(csr.out_degree)
    need = np.maximum(out_deg[src], out_deg[dst])
    per_width_arrays = {}
    lo = 0
    for w in widths:
        idx = np.nonzero((need > lo) & (need <= w))[0]
        lo = w
        e_per = max(1, -(-idx.size // n_shards))
        pad = e_per * n_shards - idx.size
        s = np.concatenate([src[idx], np.full(pad, -1, np.int32)])
        d = np.concatenate([dst[idx], np.full(pad, -1, np.int32)])
        per_width_arrays[w] = (
            np.ascontiguousarray(s.reshape(e_per, n_shards).T.astype(np.int32)),
            np.ascontiguousarray(d.reshape(e_per, n_shards).T.astype(np.int32)),
        )
    if int(need.max() if need.size else 0) > widths[-1]:
        raise ValueError("widths too small for max out-degree")
    fn, ws = make_distributed_panel_count_fn(
        mesh, {w: per_width_arrays[w][0].shape[1] for w in widths}
    )
    rep_sh = NamedSharding(mesh, P())
    edge_sh = NamedSharding(mesh, P(mesh.axis_names))
    args = [jax.device_put(per_width_arrays[w][0], edge_sh) for w in ws]
    args += [jax.device_put(per_width_arrays[w][1], edge_sh) for w in ws]
    args += [
        jax.device_put(np.asarray(csr.row_offsets), rep_sh),
        jax.device_put(np.asarray(csr.col), rep_sh),
        jax.device_put(np.asarray(csr.out_degree), rep_sh),
    ]
    partials = fn(*args)
    return int(np.asarray(partials).astype(np.uint64).sum())


def oriented_csr_from_slabs(slabs) -> OrientedCSR:
    """Orient a sharded ``.tricsr`` cache (per-stripe slab views) host-side.

    ``slabs`` are :class:`repro.graphs.io.CSRStripe` views (duck-typed:
    anything with ``row_offsets``/``col``/``node_lo``/``node_hi``/
    ``stripe_index``), each memory-mapping only its node-range slab of
    the undirected CSR.  Degrees come from the concatenated row offsets
    (tiny — one int64 per node); each slab is then oriented independently
    with the engine's forward rule ``(du < dv) | ((du == dv) & (u < v))``
    and the kept edges concatenated.  Because slabs cover contiguous
    node ranges and each slab's CSR is (src, dst)-sorted, the concat *is*
    the globally sorted oriented edge list — bit-identical to
    ``oriented_from_undirected_csr`` of the assembled CSR, without ever
    materializing the full ``col`` array on one host.
    """
    slabs = sorted(slabs, key=lambda s: int(s.stripe_index))
    if not slabs:
        raise ValueError("no slabs given")
    lo = 0
    for s in slabs:
        if int(s.node_lo) != lo:
            raise ValueError(
                f"slab {s.stripe_index} starts at node {s.node_lo}, expected {lo}"
            )
        lo = int(s.node_hi)
    n = lo
    row_full = np.concatenate(
        [np.asarray(s.row_offsets[:-1]) for s in slabs]
        + [np.asarray(slabs[-1].row_offsets[-1:])]
    ).astype(np.int64)
    deg = np.diff(row_full).astype(np.int32)
    src_parts, col_parts = [], []
    for s in slabs:
        lens = np.diff(np.asarray(s.row_offsets)).astype(np.int64)
        u = np.repeat(
            np.arange(int(s.node_lo), int(s.node_hi), dtype=np.int32), lens
        )
        v = np.asarray(s.col, dtype=np.int32)
        du, dv = deg[u], deg[v]
        keep = (du < dv) | ((du == dv) & (u < v))
        src_parts.append(u[keep])
        col_parts.append(v[keep])
    src = np.concatenate(src_parts) if src_parts else np.zeros(0, np.int32)
    col = np.concatenate(col_parts) if col_parts else np.zeros(0, np.int32)
    ensure_fits_int32(src.shape[0], "directed edge count (slab assembly offsets)")
    row = np.searchsorted(src, np.arange(n + 1, dtype=np.int64)).astype(np.int32)
    out_degree = (row[1:] - row[:-1]).astype(np.int32)
    return OrientedCSR(
        row_offsets=jnp.asarray(row),
        src=jnp.asarray(src),
        col=jnp.asarray(col),
        out_degree=jnp.asarray(out_degree),
        degree=jnp.asarray(deg),
    )


def count_triangles_distributed_slabs(
    slabs,
    mesh: Mesh,
    *,
    shorter_side: bool = False,
    max_wedge_chunk: int | None = None,
    stats_out: dict | None = None,
) -> int:
    """§III-E count straight from sharded ``.tricsr`` slab views.

    Each device's host memmaps only its slab during orientation
    (:func:`oriented_csr_from_slabs`); the oriented CSR is then
    replicated — the paper's scheme — and counted with the striped
    kernels under the usual wedge budget.
    """
    csr = oriented_csr_from_slabs(slabs)
    if int(np.asarray(csr.src).shape[0]) == 0:
        if stats_out is not None:
            stats_out.update(n_chunks=0, peak_wedge_buffer=0, cols_per_chunk=0)
        return 0
    return count_triangles_distributed_csr(
        csr, mesh,
        shorter_side=shorter_side,
        max_wedge_chunk=max_wedge_chunk,
        stats_out=stats_out,
    )
