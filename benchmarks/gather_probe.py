"""Probe of the panel gather and the wedge chunk kernel on one device.

    python -m benchmarks.gather_probe [--scale 16] [--out DIR]

Three parts, one process, every input made from a seed:

1. Isolated gathers from a sorted ``col`` of 2²⁵ int32 (2¹⁶ on the CPU):
   ``B·W`` slots per call, ``B = budget // W``.  Each panel-gather form
   (``gather_elements``, ``gather_windows`` and its row-slice variant)
   reads windows at random distinct starts and at starts repeated in
   runs of 64 rows — the engine's pattern, where a bucket's edges are
   sorted by source and repeat ``u``.  A flat ``col[idx]`` gather of
   ``budget`` slots does the same with runs of 16 (the wedge
   expansion's pattern).  Compile seconds and warm slots/s.
2. The Pallas count plan of a Kronecker graph (edge factor 16, seed
   1503): every chunk's panel gather in each form (first call and warm)
   and the kernel alone, summed per bucket width; then one profiler
   trace of all the chunks' gathers in each form, its device ops summed
   by name.
3. The first wedge chunk of the same graph: ``chunk_count_kernel`` warm
   and its device op breakdown.

Traces go under ``--out``.  Times are wall clock around
``block_until_ready``; the op breakdowns come from the profiler's device
lines (the host's XLA lines on the CPU, where the numbers measure the
CPU backend and nothing of a chip).
"""
from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prepare_oriented
from repro.core.count import gather_elements, gather_windows
from repro.core.engine import (
    PallasBackend,
    WedgeBackend,
    chunk_count_kernel,
    workload_from_csr,
)
from repro.graphs import kronecker_rmat
from repro.kernels.triangle_count import ops as tc_ops
from repro.launch.compile_cache import enable_compile_cache

def gather_row_slices(col, base, length, width):
    """``gather_windows`` with each window's ``k`` rows fetched as one
    ``(k, 128)`` dynamic slice instead of a ``(B, k)`` row-index gather
    (same result)."""
    k = -(-width // 128) + 1
    m = col.shape[0]
    n_rows = -(-m // 128) + k
    rows2d = jnp.pad(col, (0, n_rows * 128 - m), constant_values=-1).reshape(n_rows, 128)
    base = jnp.clip(base, 0, m)
    x = jax.vmap(lambda r: jax.lax.dynamic_slice(rows2d, (r, 0), (k, 128)))(base // 128)
    x = x.reshape(base.shape[0], k * 128)
    shift = base % 128
    for bit in range(7):
        x = jnp.where(((shift >> bit) & 1)[:, None] == 1, jnp.roll(x, -(1 << bit), axis=1), x)
    lane = jnp.arange(width, dtype=jnp.int32)
    return jnp.where(lane[None, :] < length[:, None], x[:, :width], -1)


FORMS = {"elements": gather_elements, "windows": gather_windows,
         "row_slices": gather_row_slices}


def log(msg: str) -> None:
    print(msg, flush=True)


def warm_seconds(fn, reps: int = 3) -> float:
    return cold_warm(fn, reps)[1]


def cold_warm(fn, reps: int = 3) -> tuple[float, float]:
    """Seconds of the first call (compile included) and the best of ``reps``."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold, best = time.perf_counter() - t0, float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return cold, best


def compiled(fn, *args, **static):
    """``(executable, compile seconds)`` of ``jax.jit(fn)`` at ``args``."""
    t0 = time.perf_counter()
    exe = jax.jit(fn, static_argnames=tuple(static)).lower(*args, **static).compile()
    return exe, time.perf_counter() - t0


def op_breakdown(trace_dir: str, top: int = 12) -> list[tuple[str, float, str]]:
    """``(op, device seconds, hlo category)`` summed over a trace, largest first."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    planes = [p for p in data.planes if p.name.startswith("/device:")]
    on_device = bool(planes)
    if not on_device:
        planes = [p for p in data.planes if p.name.startswith("/host:")]
    total, kind = collections.Counter(), {}
    for plane in planes:
        for line in plane.lines:
            if (line.name != "XLA Ops") if on_device else ("XLA" not in line.name):
                continue
            for ev in line.events:
                total[ev.name] += ev.duration_ns * 1e-9
                if ev.name not in kind:
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(ev.stats)
                    kind[ev.name] = str(stats.get("hlo_category", stats.get("long_name", "")))[:120]
    return [(name, s, kind[name]) for name, s in total.most_common(top)]


def traced(trace_dir: str, fn) -> list[tuple[str, float, str]]:
    jax.block_until_ready(fn())
    with jax.profiler.trace(trace_dir):
        jax.block_until_ready(fn())
    return op_breakdown(trace_dir)


def log_ops(title: str, ops) -> None:
    log(f"  {title}: device seconds by op")
    for name, s, cat in ops:
        log(f"    {s:.6f}  {name}  [{cat}]")


def isolated(rng, m: int, budget: int, widths) -> list[dict]:
    col = jnp.asarray(np.sort(rng.integers(0, 1 << 21, m, dtype=np.int32)))
    rows = []
    for w in widths:
        b = budget // w
        distinct = rng.integers(0, m - w, b, dtype=np.int32)
        runs = np.repeat(distinct[: -(-b // 64)], 64)[:b]
        length = jnp.asarray(rng.integers(1, w + 1, b, dtype=np.int32))
        want = None
        for pattern, starts in (("distinct", distinct), ("runs64", runs)):
            base = jnp.asarray(starts)
            for form, fn in FORMS.items():
                exe, c_s = compiled(fn, col, base, length, width=w)
                out = exe(col, base, length)
                if pattern == "distinct":
                    want = np.asarray(out) if want is None else want
                    assert np.array_equal(np.asarray(out), want), (form, w)
                s = warm_seconds(lambda: exe(col, base, length))
                rows.append(dict(part="isolated", form=form, pattern=pattern, width=w,
                                 rows=b, compile_s=c_s, warm_s=s, slots_per_s=b * w / s))
                log(f"  {form:8s} W={w:5d} {pattern:8s}: compile={c_s:.3f}s "
                    f"warm={s:.6f}s slots/s={b * w / s:.3e}")
    for pattern, run in (("distinct", 1), ("runs16", 16)):
        idx = np.repeat(rng.integers(0, m, -(-budget // run), dtype=np.int32), run)[:budget]
        idx = jnp.asarray(idx)
        exe, c_s = compiled(lambda c, i: c[i], col, idx)
        s = warm_seconds(lambda: exe(col, idx))
        rows.append(dict(part="isolated", form="flat", pattern=pattern, width=1,
                         rows=budget, compile_s=c_s, warm_s=s, slots_per_s=budget / s))
        log(f"  flat col[idx] {pattern:8s}: compile={c_s:.3f}s warm={s:.6f}s "
            f"slots/s={budget / s:.3e}")
    return rows


@functools.partial(jax.jit, static_argnames=("width", "form"))
def panels(row_offsets, col, out_degree, u, v, *, width, form):
    """``gather_panels_arrays`` with the gather form chosen by name."""
    g = FORMS[form]
    valid = (u >= 0) & (v >= 0)
    su, sv = jnp.maximum(u, 0), jnp.maximum(v, 0)
    a_len = jnp.where(valid, out_degree[su], 0)
    b_len = jnp.where(valid, out_degree[sv], 0)
    return (g(col, row_offsets[su], a_len, width), g(col, row_offsets[sv], b_len, width))


def in_engine(csr, budget: int, out: str) -> list[dict]:
    work = workload_from_csr(csr)
    chunks = list(PallasBackend().plan(work, budget).chunks)
    adj = (jnp.asarray(work.row_offsets), jnp.asarray(work.col), jnp.asarray(work.out_degree))
    dev = [(c.width, jnp.asarray(c.u), jnp.asarray(c.v)) for c in chunks]
    rows, per_width = [], collections.defaultdict(lambda: collections.Counter())
    for form in FORMS:
        for w, u, v in dev:
            cold, s = cold_warm(lambda: panels(*adj, u, v, width=w, form=form))
            per_width[w][form] += s
            per_width[w][form + "_cold"] += cold
            per_width[w]["slots"] += 2 * u.shape[0] * w if form == "windows" else 0
    for w, u, v in dev:
        a, b = panels(*adj, u, v, width=w, form="windows")
        per_width[w]["kernel"] += warm_seconds(lambda: tc_ops.intersect_count(a, b))
    for w in sorted(per_width):
        t = per_width[w]
        rows.append(dict(part="in_engine", width=w, slots=t["slots"], kernel=t["kernel"], **{
            k: t[k] for f in FORMS for k in (f, f + "_cold")}))
        log(f"  W={w:5d}: gather warm " + " ".join(f"{f}={t[f]:.6f}s" for f in FORMS)
            + "; first call " + " ".join(f"{f}={t[f + '_cold']:.3f}s" for f in FORMS)
            + f"; kernel warm={t['kernel']:.6f}s slots={t['slots']}")
    for form in FORMS:
        ops = traced(os.path.join(out, f"panels_{form}"), lambda: [
            panels(*adj, u, v, width=w, form=form) for w, u, v in dev])
        log_ops(f"all {len(dev)} chunks' gathers, {form}", ops)
        rows.append(dict(part="in_engine_trace", form=form, ops=ops))
    return rows


def wedge_chunk(csr, budget: int, out: str) -> list[dict]:
    work = workload_from_csr(csr)
    chunk = next(iter(WedgeBackend().plan(work, budget).chunks))
    args = (jnp.asarray(chunk.src), jnp.asarray(chunk.dst), jnp.asarray(work.row_offsets),
            jnp.asarray(work.col), jnp.asarray(work.out_degree))

    def run():
        return chunk_count_kernel(*args, wedge_budget=chunk.buffer, n_steps=work.n_steps)

    s = warm_seconds(run)
    log(f"  chunk_count_kernel: {chunk.buffer} slots, {work.n_steps} search steps, "
        f"warm={s:.6f}s slots/s={chunk.buffer / s:.3e}")
    ops = traced(os.path.join(out, "wedge_chunk"), run)
    log_ops("wedge chunk", ops)
    return [dict(part="wedge", slots=chunk.buffer, warm_s=s, ops=ops)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help="Kronecker scale (default: 16, or 10 on the CPU)")
    ap.add_argument("--out", default=os.path.join("chiprun_out", "gather_probe"))
    args = ap.parse_args(argv)
    enable_compile_cache()
    cpu = jax.default_backend() == "cpu"
    scale = args.scale or (10 if cpu else 16)
    m, budget = (1 << 16, 1 << 14) if cpu else (1 << 25, 1 << 24)
    widths = (16, 64, 256) if cpu else (16, 64, 256, 1024, 4096)
    os.makedirs(args.out, exist_ok=True)
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind}; jax {jax.__version__}")
    rng = np.random.default_rng(0)
    log("== isolated gathers")
    rows = isolated(rng, m, budget, widths)
    csr = prepare_oriented(kronecker_rmat(scale, edge_factor=16, seed=1503))
    log(f"== in-engine panels, Kronecker-{scale}")
    rows += in_engine(csr, budget, args.out)
    log(f"== wedge chunk, Kronecker-{scale}")
    rows += wedge_chunk(csr, budget, args.out)
    with open(os.path.join(args.out, "probe.json"), "w") as f:
        json.dump(dict(device=dev.device_kind, scale=scale, rows=rows), f, indent=1)


if __name__ == "__main__":
    main()
