#!/usr/bin/env python3
"""Bring-up smoke test: triangle counting on the TPU through its entry points.

    python chip_smoke.py              # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4    # the §III-E striped schedule on 4 chips

Everything runs in this one process (a chip belongs to one process) and
every graph is generated from a seed.  One-chip phases:

(a) device   — print the device; exit non-zero unless it is a TPU.
(b) karate   — 45 triangles with wedge_bsearch, panel and pallas; the
               Pallas kernel must run natively (``tpu_custom_call`` in its
               HLO, ``EngineStats.method == "pallas"``).
(c) kron-16  — Graph500 Kronecker scale 16, edge factor 16, seed 1503:
               the count equals ``count_triangles_numpy``; per-node and
               per-edge support are identical across wedge_bsearch and
               pallas; Σ per_node == Σ support == 3T.
(d) kron-21  — the same generator at scale 21 (the size of the registry's
               soc-livejournal stand-in), generated once on the host:
               ``repro.launch.count --json --method auto`` under a bounded
               wedge budget, then a warm count of the resolved backend, a
               count by a second backend, and the engine's panel gather
               checked against host slices of ``col`` at every bucket
               width and at the tail of ``col``.
(e) serving  — a GraphService answers count/clustering queries on a
               resident graph and a StreamSession applies insert/delete
               batches; both are checked against an engine recount.

With ``--chips 4`` only the distributed count, per_node and edge_support
run on a 4-device mesh, each against the one-device pallas result, at
scale ``DIST_SCALE``: the striped schedule runs the wedge kernels, whose
gathers close only a few million wedges per second on one v5e chip, so
scale 21 does not fit one call (PERF.md).  The pallas backend equals the
wedge backend bit for bit in phase (c).

Earlier lines report, per phase, cold (compile included) and warm times
measured around ``block_until_ready``, chunks, the executed method,
fallback reasons and the device's peak memory.  The last line is one
JSON object naming the device; it is printed only when every phase
passed on a TPU.  Any failure exits non-zero.  ``--rehearse`` runs the
same phases on the CPU backend (Pallas in interpret mode, small
``--scale``) to find faults before a chip run; it never prints the
final line and exits 3.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 1503
EDGE_FACTOR = 16
WEDGE_BUDGET = 1 << 24   # wedge slots per launch: ~0.6 GB of kernel temps
LARGE_SCALE = 21         # phase (d): the registry's soc-livejournal stand-in
DIST_SCALE = 18          # --chips 4: the largest scale its call fits (PERF.md)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device=None) -> int | None:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn):
    """``(result, seconds)`` with the result synced to the host."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def cold_warm(fn):
    """Run ``fn`` twice: the first call compiles, the second is warm."""
    first, cold = timed(fn)
    second, warm = timed(fn)
    return first, second, cold, warm


def stats_line(tc) -> str:
    st = tc.last_stats
    return (f"method={st.method} resolved={st.resolved_method} "
            f"chunks={st.n_chunks} peak_buffer={st.peak_wedge_buffer} "
            f"fallback={st.fallback_reason!r}")


def kron(scale: int) -> np.ndarray:
    from repro.graphs import kronecker_rmat

    return kronecker_rmat(scale, edge_factor=EDGE_FACTOR, seed=SEED)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_karate(args) -> None:
    import jax.numpy as jnp

    from repro.core import TriangleCounter
    from repro.graphs import canonicalize_edges
    from repro.kernels.triangle_count import intersect_count_pallas

    raw = np.loadtxt(os.path.join(HERE, "tests", "data", "karate.txt"), dtype=np.int64)
    edges = canonicalize_edges(raw)
    results = {}
    for method in ("wedge_bsearch", "panel", "pallas"):
        tc = TriangleCounter(method=method)
        t, t2, cold, warm = cold_warm(lambda: tc.count(edges))
        assert t == t2 == 45, (method, t, t2)
        assert tc.last_stats.method == method, stats_line(tc)
        results[method] = (tc.per_node(edges), tc.edge_support(edges))
        log(f"  karate {method}: T={t} cold={cold:.3f}s warm={warm:.3f}s {stats_line(tc)}")
    for method in ("panel", "pallas"):
        for got, want, what in zip(results[method], results["wedge_bsearch"],
                                   ("per_node", "support")):
            assert np.array_equal(got, want), (method, what)
    # the kernel as the engine dispatches it: compiled, not interpreted
    a = jnp.asarray(np.arange(16, dtype=np.int32)[None].repeat(8, 0))
    hlo = jax.jit(intersect_count_pallas).lower(a, a).as_text()
    native = "tpu_custom_call" in hlo
    log(f"  pallas kernel lowers to tpu_custom_call: {native}")
    if jax.default_backend() == "tpu":
        assert native, "the Pallas kernel did not lower to a Mosaic custom call"


def phase_kron_small(args) -> None:
    from repro.core import TriangleCounter, count_triangles_numpy, prepare_oriented

    edges = kron(args.small_scale)
    expect = count_triangles_numpy(edges)
    csr = prepare_oriented(edges)
    log(f"  kron-{args.small_scale}: {edges.shape[0] // 2} edges, "
        f"numpy T={expect}, max out-degree {int(np.asarray(csr.out_degree).max())}")
    outs = {}
    for method in ("wedge_bsearch", "pallas"):
        tc = TriangleCounter(method=method, max_wedge_chunk=WEDGE_BUDGET)
        t, _, cold, warm = cold_warm(lambda: tc.count(csr))
        assert t == expect, (method, t, expect)
        assert tc.last_stats.method == method, stats_line(tc)
        log(f"  count {method}: cold={cold:.3f}s warm={warm:.3f}s {stats_line(tc)}")
        # one run each (compile included): the count above already shows
        # how far warm runs beat cold ones on this graph
        pn, cold = timed(lambda: tc.per_node(csr))
        log(f"  per_node {method}: cold={cold:.3f}s {stats_line(tc)}")
        sup, cold = timed(lambda: tc.edge_support(csr))
        log(f"  support {method}: cold={cold:.3f}s {stats_line(tc)}")
        assert tc.last_stats.method == method, stats_line(tc)
        assert int(pn.sum()) == 3 * expect, (method, int(pn.sum()))
        assert int(sup.sum()) == 3 * expect, (method, int(sup.sum()))
        outs[method] = (pn, sup)
    assert np.array_equal(outs["pallas"][0], outs["wedge_bsearch"][0]), "per_node"
    assert np.array_equal(outs["pallas"][1], outs["wedge_bsearch"][1]), "support"


def run_count_cli(argv: list[str], edges: np.ndarray) -> dict:
    """``python -m repro.launch.count ARGV --json`` in this process; its record.

    ``edges`` is the generator's output for ARGV, built once by the caller.
    """
    from repro.launch.count import main as count_main

    log(f"  python -m repro.launch.count {' '.join(argv)}")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        count_main(argv, edges=edges)
    rec = json.loads(buf.getvalue().strip().splitlines()[-1])
    st = rec["stats"]
    log(f"  cli: T={rec['triangles']} method={rec['method']} "
        f"resolved={rec['resolved_method']} chunks={st['n_chunks']} "
        f"peak_buffer={st['peak_wedge_buffer']} fallback={st['fallback_reason']!r} "
        f"count={rec['timings_s']['count']:.3f}s (compile included) "
        f"timings={st['timings']} "
        f"graph={rec['graph']['n_nodes']} nodes/{rec['graph']['n_edges']} edges "
        f"max_deg={rec['graph']['max_degree']} peak device bytes {peak_bytes()}")
    return rec


def check_panels(csr) -> str:
    """The engine's panel gather equals host slices of ``col``.

    Checked on the first and the last chunk of every bucket width and on
    the chunk holding the last directed edge, whose source row ends at
    the tail of ``col``.  The host side indexes ``col`` element by
    element, independently of the device's row gather.
    """
    import jax.numpy as jnp

    from repro.core.count import gather_panels_arrays
    from repro.core.engine import PallasBackend, workload_from_csr

    work = workload_from_csr(csr)
    chunks = list(PallasBackend().plan(work, WEDGE_BUDGET).chunks)
    last_edge = work.src_host.shape[0] - 1
    picks = {}
    for i, c in enumerate(chunks):
        picks.setdefault(("first", c.width), i)
        picks[("last", c.width)] = i
        if (c.edge_idx == last_edge).any():
            picks["tail"] = i
    assert "tail" in picks, "no chunk holds the last directed edge"
    ro, col, deg = (np.asarray(x) for x in (work.row_offsets, work.col, work.out_degree))
    on_dev = tuple(jnp.asarray(x) for x in (ro, col, deg))
    for i in sorted(set(picks.values())):
        c = chunks[i]
        panels = gather_panels_arrays(*on_dev, jnp.asarray(c.u), jnp.asarray(c.v), c.width)
        lane = np.arange(c.width)
        valid = (c.u >= 0) & (c.v >= 0)
        for ends, got in ((c.u, panels[0]), (c.v, panels[1])):
            node = np.maximum(ends, 0)
            length = np.where(valid, deg[node], 0)
            idx = np.minimum(ro[node][:, None] + lane, col.size - 1)
            want = np.where(lane < length[:, None], col[idx], -1)
            assert np.array_equal(np.asarray(got), want), (c.width, i)
    widths = sorted({c.width for c in chunks})
    return f"{len(set(picks.values()))} of {len(chunks)} chunks, bucket widths {widths}"


def phase_kron_large(args) -> None:
    from repro.core import TriangleCounter, prepare_oriented

    edges, build_s = timed(lambda: kron(args.scale))
    log(f"  kron-{args.scale}: generated on the host in {build_s:.3f}s")
    argv = ["--generator", "kronecker", "--scale", str(args.scale),
            "--edge-factor", str(EDGE_FACTOR), "--seed", str(SEED),
            "--max-wedge-chunk", str(WEDGE_BUDGET), "--json", "--method", "auto"]
    rec = run_count_cli(argv, edges)
    t, method = rec["triangles"], rec["method"]
    log(f"  T > 2^31 (uint64 fold exercised): {t > 2**31 - 1}")

    csr, prep_s = timed(lambda: prepare_oriented(edges))
    del edges
    tc = TriangleCounter(method=method, max_wedge_chunk=WEDGE_BUDGET)
    warm_t, warm = timed(lambda: tc.count(csr))
    assert warm_t == t, (warm_t, t)
    log(f"  preprocess {prep_s:.3f}s; count {method} warm={warm:.3f}s {stats_line(tc)} "
        f"timings={tc.last_stats.timings}")
    other = "panel" if method != "panel" else "wedge_bsearch"
    ref = TriangleCounter(method=other, max_wedge_chunk=WEDGE_BUDGET)
    ref_t, ref_s = timed(lambda: ref.count(csr))
    assert ref.last_stats.method == other, stats_line(ref)
    assert ref_t == t, (other, ref_t, method, t)
    log(f"  second backend: count {other} T={ref_t} cold={ref_s:.3f}s {stats_line(ref)}")
    panels, check_s = timed(lambda: check_panels(csr))
    log(f"  panel gather equals host slices on {panels} ({check_s:.3f}s)")


def phase_serving(args) -> None:
    from repro.core import TriangleCounter, degree_histogram
    from repro.graphs import STREAM_GENERATORS
    from repro.serve import GraphManager, GraphService

    cache_dir = os.path.join(HERE, ".tricsr-cache", "chip_smoke")
    os.makedirs(cache_dir, exist_ok=True)
    edges = kron(args.small_scale)
    path = os.path.join(cache_dir, f"kron{args.small_scale}_seed{SEED}.txt")
    half = edges[edges[:, 0] < edges[:, 1]]
    np.savetxt(path, half, fmt="%d", delimiter="\t")
    engine = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=WEDGE_BUDGET)
    with GraphService(GraphManager(cache_dir), max_wedge_chunk=WEDGE_BUDGET) as svc:
        svc.attach("kron", path)
        t_cold, cold = timed(lambda: svc.query("kron", "count", timeout=600))
        t_warm, warm = timed(lambda: svc.query("kron", "count", timeout=600))
        cc, cc_s = timed(lambda: svc.query("kron", "clustering", timeout=600))
        with svc.manager.lease("kron") as ent:
            want_t = engine.count(ent.csr)
            deg, _ = degree_histogram(ent.csr)
            from repro.core.clustering import clustering_from_counts

            want_cc = clustering_from_counts(engine.per_node(ent.csr), deg)
        assert t_cold == t_warm == want_t, (t_cold, t_warm, want_t)
        assert np.array_equal(cc, want_cc), "clustering"
        log(f"  service count T={t_cold} cold={cold:.3f}s warm={warm:.3f}s "
            f"clustering={cc_s:.3f}s")

        n_nodes = int(edges.max()) + 1
        session = svc.open_session("stream", n_nodes=n_nodes)
        stream = STREAM_GENERATORS["sliding_window"](
            edges, window=1 << 15, batch_size=1 << 13, seed=SEED)
        batches = [b for _, b in zip(range(6), stream)]
        times = []
        for b in batches:
            _, dt = timed(lambda: svc.update("stream", insert=b.insert,
                                             delete=b.delete).result(600))
            times.append(dt)
        live, n = session.edges_snapshot()
        got = svc.query("stream", "count", timeout=600)
        want = engine.count(live, n)
        assert got == want, (got, want)
        n_del = sum(0 if b.delete is None else len(b.delete) for b in batches)
        log(f"  stream: {len(batches)} batches ({n_del} deletes), T={got} "
            f"first={times[0]:.3f}s rest={sum(times[1:]):.3f}s")


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def phase_distributed(args) -> None:
    from repro.core import TriangleCounter, prepare_oriented
    from repro.launch.mesh import make_local_mesh

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = make_local_mesh()
    edges = kron(args.scale)
    csr, prep_s = timed(lambda: prepare_oriented(edges))
    del edges
    log(f"  kron-{args.scale}: preprocess {prep_s:.3f}s, mesh {dict(mesh.shape)}")
    dist = TriangleCounter(method="distributed", mesh=mesh, max_wedge_chunk=WEDGE_BUDGET)
    ref = TriangleCounter(method="pallas", max_wedge_chunk=WEDGE_BUDGET)
    for kind in ("count", "per_node", "edge_support"):
        got, d_s = timed(lambda: getattr(dist, kind)(csr))
        st = dist.last_stats
        assert st.method == "distributed" and st.n_stripes == len(devs), stats_line(dist)
        log(f"  {kind} distributed: {d_s:.3f}s n_stripes={st.n_stripes} "
            f"stripe_skew={st.stripe_skew} chunks={st.n_chunks} "
            f"peak_buffer={st.peak_wedge_buffer}")
        want, r_s = timed(lambda: getattr(ref, kind)(csr))
        assert ref.last_stats.method == "pallas", stats_line(ref)
        log(f"  {kind} pallas (one device): {r_s:.3f}s {stats_line(ref)}")
        if kind == "count":
            assert got == want, (got, want)
            log(f"  T={got}")
        else:
            assert np.array_equal(got, want), kind
    peaks = [peak_bytes(d) for d in devs]
    log(f"  per-device peak bytes: {peaks}")
    if None not in peaks:  # the CPU backend keeps no memory stats
        assert all(peaks[1:]), "devices 1.. ran nothing"


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=None,
                    help=f"Kronecker scale of the large graph (default: {LARGE_SCALE}, "
                         f"or {DIST_SCALE} with --chips 4)")
    ap.add_argument("--small-scale", type=int, default=16,
                    help="Kronecker scale of the small graph (default: %(default)s)")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU backend (Pallas interpreted); never "
                         "reports a result")
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = DIST_SCALE if args.chips == 4 else LARGE_SCALE

    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    log(f"(a) device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} backend={jax.default_backend()} jax={jax.__version__} "
        f"compile_cache={cache}")
    if dev.platform != "tpu" and not args.rehearse:
        log("FAIL: no TPU found; this smoke test runs only on the chip")
        return 1

    if args.chips == 4:
        phases = [("distributed", phase_distributed)]
    else:
        # the long scale-21 phase runs last, so a slow chip still reports
        # every other phase
        phases = [("karate", phase_karate), ("kron-small", phase_kron_small),
                  ("serving", phase_serving), ("kron-large", phase_kron_large)]
    failed = []
    for name, fn in phases:
        log(f"== phase {name}")
        t0 = time.perf_counter()
        try:
            fn(args)
        except Exception:
            traceback.print_exc(file=sys.stdout)
            failed.append(name)
            log(f"== phase {name}: FAILED")
            continue
        log(f"== phase {name}: ok in {time.perf_counter() - t0:.3f}s, "
            f"peak device bytes {peak_bytes()}")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    if args.rehearse:
        log(f"rehearsal passed on {dev.platform}; not a chip run, no result")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
