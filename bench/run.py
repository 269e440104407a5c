"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload kron17.count --seed 7 --seconds 30 --trace 0
    python3 -m bench.run ...                                  (the same)

The cell, its configuration and its traffic mix are looked up by name:
the cell in ``BENCHMARK.json``, the configuration in the file it names,
the traffic in ``bench/traffic/<traffic>.json``, which names its arrival
loop (``bench/loops/``), and every metric in ``bench/metrics/<name>.py``.

A run:

1. has the loop set its deployment up from the configuration, the traffic
   and ``--seed``: the graphs, generated by the benchmark's own
   generators and relabelled from the seed, and the program that answers
   on them (``bench/loops/closed.py``: one resident ``OrientedCSR`` and a
   ``TriangleCounter``; ``bench/loops/open.py``: a ``GraphService``);
2. warms every shape up;
3. measures for ``--seconds`` (``--trace 1``: under the profiler), then
   reads the device's peak memory, frees the program's state, computes
   the reference of each graph and answer kind the window asked, and
   compares every answer of the window with it
   (``bench/answers/<kind>.py``).

Standard error gets what the run saw (set-up steps, compiles in the
window, peak memory, the deployment's own line, the work per answer on
each graph, the trace) and, as its last lines, each number compared
beside its limit.  The last line of standard output is the result.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as far as set-up is concerned

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
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
    work: dict | None           # one answer's work (bench/work.py) where all do the same
    trace: object = None        # bench.trace.Summary of a traced window
    window_work: dict | None = None   # the work summed over the window's answers

    def total(self, key: str) -> int:
        """``key``'s work over the window: ``window_work``, or answers x ``work``."""
        if self.window_work is not None:
            return self.window_work[key]
        return len(self.latencies) * self.work[key]


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
    seconds[name] = seconds.get(name, 0.0) + time.perf_counter() - t0


def merge(compared: dict, more: dict) -> dict:
    """Compared numbers of two groups of answers: each the larger value."""
    out = dict(compared)
    for k, (v, limit) in more.items():
        out[k] = (max(v, out[k][0]), limit) if k in out else (v, limit)
    return out


def work_of(edges, n_nodes) -> dict:
    """One answer's work on a graph (``bench/work.py``)."""
    from bench import work

    out_deg, src, dst = work.oriented_edges(edges, n_nodes)
    return {"intersection_bytes": work.intersection_bytes(out_deg, src, dst),
            "compares": work.intersection_compares(out_deg, src, dst),
            "padded_compares": work.padded_compares(out_deg, src, dst),
            "max_out_degree": int(out_deg.max())}


def run_cell(spec: dict, workload: str, seed: int, seconds: float, trace: bool, *,
             config: dict | None = None, traffic: dict | None = None,
             require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object.

    ``config`` and ``traffic`` replace the cell's configuration and traffic
    mix, and ``require_tpu=False`` skips the look for a chip: all for the
    tests alone.
    """
    import repro.core  # noqa: F401  (the system under test: without it, no run)

    setup = {"import": time.perf_counter() - T_START}
    cell = by_name(spec["workloads"], workload)
    config, traffic = cell_files(spec, cell, config, traffic)
    t0 = time.perf_counter()
    devs = devices(cell["chips"], require_tpu)
    setup["attach"] = time.perf_counter() - t0

    import jax

    use_compile_cache()
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    loop = importlib.import_module(f"bench.loops.{traffic['loop']}")

    deployment = loop.setup(config, seed, traffic, functools.partial(step, seconds=setup))
    with step("warmup", setup):
        deployment.warm()
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
        window = deployment.run(seconds)
    finally:
        compiles.active = False
        if trace:
            jax.profiler.stop_trace()
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window {len(window.answers)} answers, {window.failed} failed, in "
        f"{window.window_s:.6f}s; in the window {compiles.counts['traces']} traces, "
        f"{compiles.counts['compiles']} compiles; peak_bytes_in_use={peak}")
    log(deployment.describe())
    graphs = deployment.graphs
    deployment.close()
    del deployment
    gc.collect()

    asked = sorted({a.graph for a in window.answers})
    per_graph = {name: work_of(*graphs[name]) for name in asked}
    for name in asked:
        edges, n_nodes = graphs[name]
        log(f"graph {name} n={n_nodes} undirected_edges={edges.shape[0] // 2}; per answer " +
            " ".join(f"{k}={v}" for k, v in per_graph[name].items()))
    window_work = {k: sum(per_graph[a.graph][k] for a in window.answers)
                   for k in ("intersection_bytes", "compares", "padded_compares")}

    t0 = time.perf_counter()
    failed, compared = compare_window(window.answers, graphs)
    log(f"reference took {time.perf_counter() - t0:.3f}s")

    run = Run(setup_s=setup_s, window_s=window.window_s, latencies=window.latencies,
              plan_s=window.plan_s, device_kind=devs[0].device_kind,
              work=per_graph[asked[0]] if len(asked) == 1 else None,
              window_work=window_work)
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
    correct = (bool(window.answers) and window.failed == 0
               and all(v <= limit for v, limit in compared.values()))
    result = {"correct": correct, "attempted": len(window.answers) + window.failed,
              "failed": failed + window.failed, "metrics": metrics, "device": device,
              **breakdown,
              "compared": {k: {"value": v, "limit": limit} for k, (v, limit) in compared.items()}}
    record = dict(workload=workload, seed=seed, seconds=seconds, trace=trace, setup=setup,
                  answers=[{"graph": a.graph, "kind": a.kind, "latency_s": a.latency_s}
                           for a in window.answers],
                  plan_s=window.plan_s, compiles=compiles.counts, work=per_graph,
                  window_work=window_work, result=result)
    if trace:
        from bench import spans

        s = spans.of(run)
        record["span_idle_s"] = s.span_idle_s if s else None
    os.makedirs(os.path.join(CACHE, "runs"), exist_ok=True)
    with open(os.path.join(CACHE, "runs", f"{workload}.{seed}.{int(trace)}.json"), "w") as f:
        json.dump(record, f)
    for k, (v, limit) in compared.items():
        log(f"compared {k}={v} limit={limit}")
    return result


def cell_files(spec: dict, cell: dict, config: dict | None, traffic: dict | None) -> tuple:
    """The cell's configuration and traffic mix, unless given."""
    if config is None:
        config = load_json(os.path.join(ROOT, by_name(spec["configs"], cell["config"])["file"]))
    if traffic is None:
        traffic = load_json(os.path.join(ROOT, "bench", "traffic", f"{cell['traffic']}.json"))
    return config, traffic


def compare_window(answers: list, graphs: dict) -> tuple:
    """``(failed, compared)`` of the window's answers: each graph and answer
    kind's reference computed once, each group compared by its kind's
    ``compare``, failures summed and each compared number the largest."""
    groups = {}
    for a in answers:
        groups.setdefault((a.graph, a.kind), []).append(a.value)
    failed, compared = 0, {}
    for (graph, kind), values in sorted(groups.items()):
        answer = importlib.import_module(f"bench.answers.{kind}")
        ref = answer.reference(*graphs[graph])
        if isinstance(ref, int):
            log(f"reference {graph} {kind}: {ref}")
        n_failed, more = answer.compare(values, ref)
        failed += n_failed
        compared = merge(compared, more)
    return failed, compared


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
