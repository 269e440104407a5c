"""repro.obs tests: span tracer, counters, histograms, exporters, and the
engine/analytics integration (spans measure phases, timings reconcile)."""

import json

import numpy as np
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts with tracing off and fresh metrics."""
    obs.reset_metrics()
    yield
    if obs.enabled():
        obs.stop_tracing()
    obs.reset_metrics()


# ---------------------------------------------------------------------------
# span tracer


def test_nested_spans_record_close_order_and_depth():
    with obs.tracing() as t:
        with obs.span("outer", cat="t"):
            with obs.span("inner_a", cat="t"):
                pass
            with obs.span("inner_b", cat="t"):
                pass
    names = [e["name"] for e in t.events]
    assert names == ["inner_a", "inner_b", "outer"]  # children close first
    depths = {e["name"]: e["depth"] for e in t.events}
    assert depths == {"outer": 0, "inner_a": 1, "inner_b": 1}
    outer = t.events[-1]
    for child in t.events[:-1]:
        assert child["ts_ns"] >= outer["ts_ns"]
        assert child["ts_ns"] + child["dur_ns"] <= outer["ts_ns"] + outer["dur_ns"]


def test_span_exception_safety_records_event_and_restores_depth():
    with obs.tracing() as t:
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        # depth restored: the next span is a sibling at depth 0
        with obs.span("after"):
            pass
    boom, after = t.events
    assert boom["name"] == "boom" and boom["error"] == "ValueError"
    assert boom["depth"] == 0 and after["depth"] == 0


def test_span_set_attaches_args():
    with obs.tracing() as t:
        with obs.span("s", args={"a": 1}) as sp:
            sp.set(b=2)
    assert t.events[0]["args"] == {"a": 1, "b": 2}


def test_disabled_span_is_the_shared_noop_singleton():
    # with no tracer a span records no event anywhere, never blocks on the
    # device (it has no sync), and still times its body from its own reads
    assert not obs.enabled()
    sp = obs.span("anything", cat="x", args={"k": 1})
    assert isinstance(sp, obs.Span) and not hasattr(sp, "sync")
    with sp as inner:
        assert inner is sp and inner.set(x=1) is sp
    assert sp.args == {"k": 1, "x": 1}
    assert sp.t1_ns >= sp.t0_ns > 0
    assert sp.seconds == (sp.t1_ns - sp.t0_ns) / 1e9
    with obs.tracing() as t:
        pass
    assert t.events == []


def test_nested_start_tracing_raises():
    obs.start_tracing()
    with pytest.raises(RuntimeError, match="already active"):
        obs.start_tracing()
    t = obs.stop_tracing()
    assert t is not None and obs.active() is None


def test_instant_records_zero_duration_marker():
    with obs.tracing() as t:
        t.instant("mark", cat="x", args={"n": 3})
    (ev,) = t.events
    assert ev["dur_ns"] == 0 and ev["args"] == {"n": 3}


# ---------------------------------------------------------------------------
# counters / gauges


def test_counter_and_gauge_round_trip():
    obs.counter("a.hits").add()
    obs.counter("a.hits").add(2)
    obs.gauge("a.level").set(7)
    obs.gauge("a.level").set(11)  # last write wins
    snap = obs.metrics_snapshot()
    assert snap["counters"]["a.hits"] == 3
    assert snap["gauges"]["a.level"] == 11
    obs.reset_metrics()
    assert obs.metrics_snapshot() == {"counters": {}, "gauges": {}}


def test_registry_isolated_instances():
    r = obs.MetricsRegistry()
    r.counter("x").add(5)
    assert r.snapshot()["counters"]["x"] == 5
    assert "x" not in obs.metrics_snapshot()["counters"]


# ---------------------------------------------------------------------------
# pow2 histograms (serve_graph latency satellite)


def test_pow2_histogram_percentiles_bracket_observations():
    h = obs.Pow2Histogram()
    for ms in (1.0, 2.0, 4.0, 8.0, 100.0):
        h.observe(ms / 1e3)
    assert h.n == 5
    p50 = h.percentile(50)
    assert 2e-3 <= p50 <= 8e-3  # seconds: median is in the 2–8ms range
    assert h.percentile(99) <= 2 * 100e-3  # p99 within bucket of the max
    snap = h.snapshot_ms()
    assert snap["n"] == 5 and snap["p99_ms"] >= snap["p50_ms"] > 0


def test_pow2_histogram_merge_adds_counts():
    a, b = obs.Pow2Histogram(), obs.Pow2Histogram()
    a.observe_ns(1000)
    b.observe_ns(1000)
    b.observe_ns(2000)
    a.merge(b)
    assert a.n == 3 and a.total_ns == 4000


def test_rolling_histogram_window_vs_lifetime():
    rh = obs.RollingHistogram(window=2)
    rh.observe(0.001)
    rh.rotate()
    rh.observe(0.002)
    rh.rotate()
    rh.observe(0.004)
    # lifetime keeps everything; the 2-interval window dropped the first
    assert rh.lifetime.n == 3
    assert rh.windowed().n == 2


# ---------------------------------------------------------------------------
# exporters + validators


def _traced_sample():
    with obs.tracing() as t:
        with obs.span("outer", cat="t"):
            with obs.span("inner", cat="t", args={"k": 1}):
                pass
    return t


def test_chrome_trace_round_trip_validates(tmp_path):
    t = _traced_sample()
    obj = obs.to_chrome_trace(t, metrics=obs.metrics_snapshot(), meta={"m": 1})
    assert obs.validate_chrome_trace(obj) == 2
    assert obj["otherData"]["schema"] == obs.SCHEMA
    assert obj["otherData"]["meta"] == {"m": 1}
    # file round trip via extension dispatch
    path = tmp_path / "trace.json"
    obs.write_trace(str(path), t)
    assert obs.validate_chrome_trace(json.loads(path.read_text())) == 2


def test_jsonl_trace_round_trip_validates(tmp_path):
    t = _traced_sample()
    path = tmp_path / "trace.jsonl"
    obs.write_trace(str(path), t, meta={"cli": "test"})
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert obs.validate_jsonl_records(records) == 2
    assert records[0]["meta"] == {"cli": "test"}
    assert records[-1]["kind"] == "metrics"


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        obs.validate_chrome_trace({})
    with pytest.raises(ValueError, match="negative"):
        obs.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": -1, "dur": 1,
                              "pid": 0, "tid": 0, "args": {"depth": 0}}]}
        )
    with pytest.raises(ValueError, match="depth"):
        obs.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0, "dur": 1,
                              "pid": 0, "tid": 0}]}
        )
    # a depth-1 span whose would-be parent doesn't contain it
    bad_nest = {
        "traceEvents": [
            {"name": "child", "ph": "X", "ts": 100.0, "dur": 50.0,
             "pid": 0, "tid": 0, "args": {"depth": 1}},
            {"name": "parent", "ph": "X", "ts": 0.0, "dur": 60.0,
             "pid": 0, "tid": 0, "args": {"depth": 0}},
        ]
    }
    with pytest.raises(ValueError, match="not contained"):
        obs.validate_chrome_trace(bad_nest)


def test_validate_jsonl_rejects_missing_header_or_tail():
    t = _traced_sample()
    records = obs.to_jsonl_records(t)
    with pytest.raises(ValueError, match="meta header"):
        obs.validate_jsonl_records(records[1:])
    with pytest.raises(ValueError, match="metrics"):
        obs.validate_jsonl_records(records[:-1])


def test_trace_to_file_none_is_noop_scope():
    with obs.trace_to_file(None) as t:
        assert t is None and not obs.enabled()


def test_trace_to_file_writes_artifact_with_counters(tmp_path):
    path = tmp_path / "t.json"
    with obs.trace_to_file(str(path), meta={"cli": "unit"}):
        obs.counter("unit.ticks").add(4)
        with obs.span("work"):
            pass
    obj = json.loads(path.read_text())
    assert obs.validate_chrome_trace(obj) == 1
    assert obj["otherData"]["metrics"]["counters"]["unit.ticks"] == 4
    assert obj["otherData"]["meta"]["cli"] == "unit"


def test_env_fingerprint_has_stdlib_and_jax_fields():
    fp = obs.env_fingerprint()
    assert fp["python"] and fp["platform"]
    assert fp["jax"] is not None  # jax is installed in the test env
    assert fp["device_count"] >= 1


# ---------------------------------------------------------------------------
# engine + analytics integration


PHASES = ("preprocess", "host_copy", "plan", "dispatch", "wait", "fold")


def test_engine_count_emits_spans_and_timings(small_graphs):
    from repro.core import TriangleCounter

    edges = small_graphs["kron"]
    tc = TriangleCounter(method="wedge_bsearch")
    t_plain = tc.count(edges)  # warm the jit cache untraced

    with obs.tracing() as t:
        t_traced = tc.count(edges)
    assert t_traced == t_plain

    names = [e["name"] for e in t.events]
    assert "engine.count" in names
    for phase in PHASES:
        assert names.count(f"engine.{phase}") == 1, (phase, names)
    assert "engine.chunk" in names

    es = tc.last_stats
    assert es.timings is not None
    assert tuple(es.timings) == PHASES
    # each timing is its span's duration, from the same clock reads
    for phase in PHASES:
        (ev,) = [e for e in t.events if e["name"] == f"engine.{phase}"]
        assert es.timings[phase] == ev["dur_ns"] / 1e9
    # the phases cover the answer's span
    span_wall = next(e for e in t.events if e["name"] == "engine.count")
    wall_s = span_wall["dur_ns"] / 1e9
    total = sum(es.timings.values())
    assert total <= wall_s
    assert total >= 0.95 * wall_s - 0.001, (total, wall_s)


def test_untraced_count_still_fills_timings(small_graphs):
    from repro.core import TriangleCounter

    tc = TriangleCounter(method="wedge_bsearch")
    tc.count(small_graphs["kron"])
    assert not obs.enabled()
    assert tc.last_stats.timings is not None
    assert tc.last_stats.timings["preprocess"] >= 0


def test_graph_report_traces_all_stages(small_graphs):
    from repro.analytics import graph_report

    graph_report(small_graphs["kron"])  # warm untraced
    with obs.tracing() as t:
        rep = graph_report(small_graphs["kron"])
    names = {e["name"] for e in t.events}
    for stage in ("report.preprocess", "report.count", "report.clustering",
                  "report.support", "report.truss"):
        assert stage in names, (stage, sorted(names))
    assert "truss.round" in names
    # exported form of the full analytics run validates
    assert obs.validate_chrome_trace(obs.to_chrome_trace(t)) == len(t.events)
    assert rep["engine"]["timings"] is not None


def test_engine_counters_accumulate(small_graphs):
    from repro.core import TriangleCounter

    TriangleCounter(method="wedge_bsearch").count(small_graphs["kron"])
    counters = obs.metrics_snapshot()["counters"]
    assert counters["engine.workloads"] == 1
    assert counters["engine.chunks_launched"] >= 1
    assert counters["engine.wedges_planned"] > 0


def test_incremental_probe_spans(small_graphs):
    from repro.core.incremental import IncrementalTriangleCounter

    tc = IncrementalTriangleCounter(small_graphs["triangle"])
    with obs.tracing() as t:
        tc.insert(np.array([[0, 9], [9, 1]]))
    names = [e["name"] for e in t.events]
    for n in ("probe.without", "probe.with", "probe.delta"):
        assert n in names, names


# ---------------------------------------------------------------------------
# spans on the profiler's timeline


def _profiled(path, fn):
    """Run ``fn`` under ``jax.profiler.trace``; its ``tc.`` events in order."""
    import glob

    import jax

    with jax.profiler.trace(str(path)):
        out = fn()
    (pb,) = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(pb)
    events = sorted(
        (ev.start_ns, -ev.duration_ns, ev.name, ev.duration_ns, dict(ev.stats))
        for plane in data.planes for line in plane.lines for ev in line.events
        if ev.name.startswith(obs.PROFILER_PREFIX)
    )
    return out, [(n, s, s + d, st) for s, _, n, d, st in events]


def _panel_csr():
    from repro.core import prepare_oriented
    from repro.graphs import kronecker_rmat

    return prepare_oriented(kronecker_rmat(8, edge_factor=8, seed=2))


def test_profiler_trace_holds_nested_engine_spans(tmp_path):
    from repro.core import TriangleCounter

    csr = _panel_csr()
    tc = TriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    want = tc.count(csr)                       # warm, untraced
    got, events = _profiled(tmp_path, lambda: tc.count(csr))
    assert got == want
    (answer,) = [e for e in events if e[0] == "tc.engine.count"]
    call = answer[3]["call"]
    assert isinstance(call, int)
    kids = [e for e in events if e is not answer]
    assert all(answer[1] <= s and e <= answer[2] and st["call"] == call
               for _, s, e, st in kids)
    by = {name: [e for e in kids if e[0] == name] for name, *_ in kids}
    for phase in PHASES:
        assert len(by[f"tc.engine.{phase}"]) == 1, phase
    (plan,) = by["tc.engine.plan"]
    (dispatch,) = by["tc.engine.dispatch"]
    assert plan[3]["edges"] == csr.n_directed_edges
    assert plan[3]["chunks"] == dispatch[3]["chunks"] == len(by["tc.engine.chunk"]) > 1
    for _, s, e, st in by["tc.engine.chunk"]:
        assert dispatch[1] <= s and e <= dispatch[2]
        assert st["width"] in (16, 64) and st["rows"] > 0
    # the warm answer kept the plan, with its index arrays on the device
    assert plan[3]["reused"] == 1
    assert dispatch[3]["slots"] > 0 and dispatch[3]["h2d_bytes"] == 0
    assert by["tc.engine.host_copy"][0][3]["bytes"] > 0
    assert by["tc.engine.fold"][0][3]["bytes"] > 0
    # the phases follow one another in answer order
    starts = [by[f"tc.engine.{p}"][0][1] for p in PHASES]
    assert starts == sorted(starts)


def test_profiler_spans_match_tracer_events(tmp_path):
    from repro.core import TriangleCounter

    csr = _panel_csr()
    tc = TriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    tc.count(csr)

    def traced():
        with obs.tracing() as t:
            tc.count(csr)
        return t

    t, events = _profiled(tmp_path, traced)
    assert len(t.events) == len(events) > 8
    for name in {e["name"] for e in t.events}:
        ours = [e["dur_ns"] for e in sorted(t.events, key=lambda e: e["ts_ns"])
                if e["name"] == name]
        theirs = [e - s for n, s, e, _ in events if n == obs.PROFILER_PREFIX + name]
        assert len(ours) == len(theirs), name
        # the annotation encloses the span's two clock reads and adds its
        # own enter and exit: a few microseconds under a profiler that
        # also traces every Python call
        for a, b in zip(ours, theirs):
            assert 0 <= b - a <= max(0.05 * a, 10e3), (name, a, b)


def test_traced_count_blocks_once(monkeypatch, small_graphs):
    import jax

    from repro.core import TriangleCounter

    tc = TriangleCounter(method="wedge_bsearch", max_wedge_chunk=256)
    want = tc.count(small_graphs["kron"])
    assert tc.last_stats.n_chunks > 1
    calls = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append(1) or block(x))
    with obs.tracing():
        assert tc.count(small_graphs["kron"]) == want
    assert len(calls) == 1


def test_dispatch_counters_match_the_plan():
    import jax

    from repro.core import TriangleCounter
    from repro.core.engine import PanelBackend, make_workload, run_workload, workload_from_csr

    csr = _panel_csr()
    chunks = list(PanelBackend().plan(workload_from_csr(csr), 1 << 10).chunks)
    tc = TriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    with obs.tracing() as t:
        tc.count(csr)
    args = {e["name"]: e.get("args", {}) for e in t.events}
    assert args["engine.dispatch"]["slots"] == sum(2 * len(c.u) * c.width for c in chunks)
    # a resident graph's kept plan put its index arrays on the device
    # when it was built: the launches upload nothing
    assert args["engine.dispatch"]["h2d_bytes"] == 0
    assert args["engine.dispatch"]["chunks"] == len(chunks) == tc.last_stats.n_chunks
    resident = (csr.src, csr.col, csr.out_degree)
    assert all(isinstance(a, jax.Array) for a in resident)
    assert args["engine.host_copy"]["bytes"] == sum(a.nbytes for a in resident)
    assert args["engine.plan"] == {"call": args["engine.count"]["call"],
                                   "edges": csr.n_directed_edges, "chunks": len(chunks),
                                   "reused": 0}
    # a workload of host arrays uploads each chunk's u and v as it launches
    host = make_workload(*(np.asarray(a) for a in (csr.row_offsets, csr.col, csr.out_degree,
                                                   csr.src, csr.col)))
    with obs.tracing() as t:
        run_workload(PanelBackend(), "count", host, budget=1 << 10)
    args = {e["name"]: e.get("args", {}) for e in t.events}
    assert args["engine.dispatch"]["h2d_bytes"] == sum(c.u.nbytes + c.v.nbytes for c in chunks)
    assert args["engine.plan"]["reused"] == 0


def test_support_chunks_count_their_scatter_lanes():
    from repro.core import TriangleCounter
    from repro.core.engine import PanelBackend, workload_from_csr

    csr = _panel_csr()
    chunks = list(PanelBackend().plan(workload_from_csr(csr), 1 << 10).chunks)
    want = sum(len(c.u) * (1 + c.width + c.width) for c in chunks)
    assert {c.width for c in chunks} == {16, 64}
    tc = TriangleCounter(method="panel", max_wedge_chunk=1 << 10)
    with obs.tracing() as t:
        tc.edge_support(csr)
    (dispatch,) = [e.get("args", {}) for e in t.events if e["name"] == "engine.dispatch"]
    # one dispatch launches every chunk: base, arm and closure lanes, padded rows included
    assert dispatch["chunks"] == len(chunks) == len(
        [e for e in t.events if e["name"] == "engine.chunk"])
    assert dispatch["scatter_lanes"] == want
    assert obs.metrics_snapshot()["counters"]["engine.scatter_lanes"] == want
    # a count of the same graph carries neither the arg nor the counter
    obs.reset_metrics()
    with obs.tracing() as t:
        tc.count(csr)
    (args,) = [e.get("args", {}) for e in t.events if e["name"] == "engine.dispatch"]
    assert "scatter_lanes" not in args and args["slots"] > 0
    assert "engine.scatter_lanes" not in obs.metrics_snapshot()["counters"]


@pytest.mark.parametrize("kind", ["per_node", "edge_support"])
def test_per_chunk_phases_of_per_node_and_support(kind, small_graphs):
    from repro.core import TriangleCounter

    tc = TriangleCounter(method="panel", max_wedge_chunk=256)
    want = getattr(tc, kind)(small_graphs["kron"])
    with obs.tracing() as t:
        got = getattr(tc, kind)(small_graphs["kron"])
    assert np.array_equal(got, want)
    n = tc.last_stats.n_chunks
    assert n > 1
    names = [e["name"] for e in t.events]
    assert names.count("engine.chunk") == n
    # per-node folds each chunk before the next one launches; support adds
    # its chunks up on the device, waits once and folds once
    per_answer = n if kind == "per_node" else 1
    for phase in ("dispatch", "wait", "fold"):
        assert names.count(f"engine.{phase}") == per_answer, phase
    es = tc.last_stats
    assert tuple(es.timings) == PHASES
    for phase in ("dispatch", "wait", "fold"):
        spans = [e["dur_ns"] for e in t.events if e["name"] == f"engine.{phase}"]
        assert es.timings[phase] == pytest.approx(sum(spans) / 1e9, rel=1e-9)
    # the untraced answer has the same phases
    getattr(tc, kind)(small_graphs["kron"])
    assert tuple(tc.last_stats.timings) == PHASES
