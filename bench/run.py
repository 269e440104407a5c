"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload kron17.count --seed 7 --seconds 30 --trace 0
    python3 -m bench.run ...                                  (the same)

The cell, its configuration and its traffic mix are looked up by name:
the cell in ``BENCHMARK.json``, the configuration in the file it names,
the traffic in ``bench/traffic/<traffic>.json``, which names its answer
kind (``bench/answers/``) and its arrival loop (``bench/loops/``), and
every metric in ``bench/metrics/<name>.py``.

A run:

1. generates the configuration's graph with the benchmark's own
   generator, and relabels its vertices from ``--seed``;
2. orients it on the device once (``repro.core.prepare_oriented``) and
   keeps the ``OrientedCSR`` resident;
3. warms every shape up with one full answer;
4. answers for ``--seconds`` (``--trace 1``: under the profiler), then
   reads the device's peak memory, frees the program's state, computes
   the reference and compares every answer of the window with it.

Standard error gets what the run saw (set-up steps, compiles in the
window, peak memory, chunks and compares per answer, the trace) and, as
its last lines, each number compared beside its limit.  The last line
of standard output is the result.  Without a TPU, or with fewer chips
than the cell asks for, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as far as set-up is concerned

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE = os.path.join(ROOT, ".bench_cache")   # compile cache, traces, run records
NO_CHIP = 2


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What the metric readers see (``bench/metrics``)."""

    setup_s: float
    window_s: float
    latencies: list
    plan_s: list
    device_kind: str
    work: dict
    trace: object = None        # bench.trace.Summary of a traced window


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries: list, name: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no entry named {name!r}; known: {[e['name'] for e in entries]}")


def cell_metrics(spec: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` this cell reports, as their entries."""
    reported_e2e = {m["name"] for m in spec["end_to_end"]
                    if cell in m.get("workloads", [cell])}
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported_e2e:
            out.append(m)
    return out


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent cache, at a fixed path of the checkout unless the
    environment names one; every program is kept, however fast it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """New traces and XLA compiles while ``active`` (JAX's monitoring events)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.active = False
        self.counts = {"traces": 0, "compiles": 0}

    def __call__(self, event, *args, **kwargs):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


@contextlib.contextmanager
def step(name: str, seconds: dict):
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"bench.setup.{name}"):
        yield
    seconds[name] = time.perf_counter() - t0


def run_cell(spec: dict, workload: str, seed: int, seconds: float, trace: bool, *,
             config: dict | None = None, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object.

    ``config`` replaces the cell's configuration and ``require_tpu=False``
    skips the look for a chip: both for the tests alone.
    """
    from repro.core import TriangleCounter, prepare_oriented

    setup = {"import": time.perf_counter() - T_START}
    cell = by_name(spec["workloads"], workload)
    if config is None:
        config = load_json(os.path.join(ROOT, by_name(spec["configs"], cell["config"])["file"]))
    traffic = load_json(os.path.join(ROOT, "bench", "traffic", f"{cell['traffic']}.json"))
    t0 = time.perf_counter()
    devs = devices(cell["chips"], require_tpu)
    setup["attach"] = time.perf_counter() - t0

    import jax

    from bench import graphs, work

    use_compile_cache()
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    answer = importlib.import_module(f"bench.answers.{traffic['answer']}")
    loop = importlib.import_module(f"bench.loops.{traffic['loop']}")

    with step("generate", setup):
        base, n_nodes = graphs.generate(config)
    with step("relabel", setup):
        edges = graphs.relabel(base, n_nodes, seed)
        del base
    with step("orient", setup):
        csr = jax.block_until_ready(prepare_oriented(edges, n_nodes))
    counter = TriangleCounter(**traffic["counter"])
    with step("warmup", setup):
        loop.warm(answer, counter, csr, traffic)
    log("set-up " + " ".join(f"{k}={v:.3f}s" for k, v in setup.items()))

    trace_dir = os.path.join(CACHE, "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level, options.python_tracer_level = 2, 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles.active = True
    # the chip's attach (libtpu's start) is the same for every version of
    # the program, and varies by seconds from run to run: not set-up
    setup_s = time.perf_counter() - T_START - setup["attach"]
    try:
        window = loop.run(answer, counter, csr, seconds, traffic)
    finally:
        compiles.active = False
        if trace:
            jax.profiler.stop_trace()
    stats = counter.last_stats
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window {len(window.answers)} answers in {window.window_s:.6f}s; "
        f"in the window {compiles.counts['traces']} traces, "
        f"{compiles.counts['compiles']} compiles; peak_bytes_in_use={peak}")
    log(f"per answer: method={stats.method} chunks={stats.n_chunks} "
        f"peak_wedge_buffer={stats.peak_wedge_buffer}")
    del csr, counter
    gc.collect()

    out_deg, src, dst = work.oriented_edges(edges, n_nodes)
    counts = {"intersection_bytes": work.intersection_bytes(out_deg, src, dst),
              "compares": work.intersection_compares(out_deg, src, dst),
              "padded_compares": work.padded_compares(out_deg, src, dst)}
    log(f"graph n={n_nodes} undirected_edges={edges.shape[0] // 2} "
        f"max_out_degree={int(out_deg.max())}; per answer " +
        " ".join(f"{k}={v}" for k, v in counts.items()))

    t0 = time.perf_counter()
    ref = answer.reference(edges, n_nodes)
    failed, compared = answer.compare(window.answers, ref)
    log(f"reference took {time.perf_counter() - t0:.3f}s" +
        (f"; triangles={ref}" if isinstance(ref, int) else ""))

    run = Run(setup_s=setup_s, window_s=window.window_s, latencies=window.latencies,
              plan_s=window.plan_s, device_kind=devs[0].device_kind, work=counts)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = {}
    if trace:
        from bench import trace as trace_mod

        path = trace_mod.xplane_path(trace_dir)
        select = (trace_mod.tpu_select(len(devs)) if devs[0].platform == "tpu"
                  else trace_mod.cpu_select)
        run.trace = trace_mod.reduce(*trace_mod.read(path, select))
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        log(f"trace {path}: {os.path.getsize(path)} bytes, busy_s={run.trace.busy_s} "
            f"window_s={run.trace.window_s}")
        breakdown["breakdown"] = {"device_ops": run.trace.top_ops(),
                                  "idle_gaps": run.trace.top_gaps()}
    metrics = {}
    for m in cell_metrics(spec, workload, "per_layer" if trace else "end_to_end"):
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = bool(window.answers) and all(v <= limit for v, limit in compared.values())
    result = {"correct": correct, "attempted": len(window.answers), "failed": failed,
              "metrics": metrics, "device": device, **breakdown,
              "compared": {k: {"value": v, "limit": limit} for k, (v, limit) in compared.items()}}
    record = dict(workload=workload, seed=seed, seconds=seconds, trace=trace, setup=setup,
                  latencies=window.latencies, plan_s=window.plan_s, compiles=compiles.counts,
                  work=counts, result=result)
    os.makedirs(os.path.join(CACHE, "runs"), exist_ok=True)
    with open(os.path.join(CACHE, "runs", f"{workload}.{seed}.{int(trace)}.json"), "w") as f:
        json.dump(record, f)
    for k, (v, limit) in compared.items():
        log(f"compared {k}={v} limit={limit}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(load_spec(), args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"no result: {e}")
        return NO_CHIP
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
