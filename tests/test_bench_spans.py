"""The benchmark's reading of the program's spans (``bench/spans.py``) and
the four metrics it feeds, on hand-made events and on a small count
recorded on the CPU (``bench/tests/make_cpu_count_trace.py``); and the
benchmark's trace reduction (``bench/trace.py``) on its committed trace,
which the program's spans must not move."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import spans, trace  # noqa: E402
from bench.run import Run  # noqa: E402
from bench.tests import make_cpu_count_trace as recorder  # noqa: E402

DATA = os.path.join(REPO, "bench", "tests", "data")
WINDOW_TRACE = os.path.join(DATA, "cpu_window.xplane.pb")
COUNT_TRACE = os.path.join(DATA, "cpu_count.xplane.pb")
PHASES = ("preprocess", "host_copy", "plan", "dispatch", "wait", "fold")
METRICS = ("host_copy_share", "dispatch_share", "fold_share", "panel_fill_share")


def test_spans_reduce_hand_made_events():
    window = (0, 100)
    events = [
        ("tc.engine.host_copy", -10, 5, {"bytes": 7}),       # starts before the window
        ("tc.engine.count", 10, 90, {"call": 0}),
        ("tc.engine.plan", 10, 30, {"call": 0, "edges": 5, "chunks": 2}),
        ("tc.engine.dispatch", 30, 60, {"chunks": 2, "slots": 64, "note": "x"}),
        ("tc.engine.chunk", 35, 45, {"width": 16, "rows": 2}),
        ("tc.engine.fold", 70, 90, {"bytes": 8}),
        ("tc.engine.wait", 95, 105, {}),                     # ends after the window
        ("tc.engine.plan", 120, 130, {"edges": 99}),         # outside the window
    ]
    ops = {"/device:TPU:0": [(40, 75), (95, 110)]}
    s = spans.reduce(window, events, ops)
    assert s.window_s == pytest.approx(100e-9)
    assert s.span_s == pytest.approx({
        "tc.engine.host_copy": 5e-9, "tc.engine.count": 80e-9, "tc.engine.plan": 20e-9,
        "tc.engine.dispatch": 30e-9, "tc.engine.chunk": 10e-9, "tc.engine.fold": 20e-9,
        "tc.engine.wait": 5e-9,
    })
    # idle [0,40] and [75,95], cut where a span starts or ends
    assert s.span_idle_s == pytest.approx({
        "tc.engine.host_copy": 5e-9,     # [0,5]
        "none": 10e-9,                   # [5,10] and [90,95]
        "tc.engine.plan": 20e-9,         # [10,30]
        "tc.engine.dispatch": 5e-9,      # [30,35]
        "tc.engine.chunk": 5e-9,         # [35,40]
        "tc.engine.fold": 15e-9,         # [75,90]
    })
    assert s.idle_s == pytest.approx(60e-9) == pytest.approx(sum(s.span_idle_s.values()))
    # stats of the spans that start inside the window, numbers only
    assert s.span_stats == {
        "tc.engine.count": {"call": 0}, "tc.engine.plan": {"call": 0, "edges": 5, "chunks": 2},
        "tc.engine.dispatch": {"chunks": 2, "slots": 64},
        "tc.engine.chunk": {"width": 16, "rows": 2}, "tc.engine.fold": {"bytes": 8},
    }


def test_trace_reduce_names_gaps_by_frames_inside_spans():
    """Program spans around the host's frames leave ``bench/trace.py``'s
    busy time, op time and gap names as they were."""
    host = [("bench.window", 0, 100), ("bench.answer.count", 5, 50),
            ("bench.answer.count", 55, 100), ("PjitFunction(f)", 0, 100),
            ("$engine.py:700 plan", 38, 62)]
    ops = {"/device:TPU:0": [("m/a", 10, 30), ("m/b", 20, 40), ("m/c", 60, 70),
                             ("m/d", 95, 120)]}
    with_spans = host + [("tc.engine.plan", 37, 63)]     # around the plan frame
    a, b = trace.reduce(host, ops), trace.reduce(with_spans, ops)
    assert (a.window_s, a.busy_s, a.op_s, a.gap_s) == (b.window_s, b.busy_s, b.op_s, b.gap_s)


def test_committed_window_trace_reduces_as_before():
    s = trace.reduce(*trace.read(WINDOW_TRACE, trace.cpu_select))
    assert s.window_s == 0.08310830400000001
    assert s.busy_s == 0.0013559470000000001
    assert s.op_s == {
        "?/ThreadpoolListener::StartRegion": 0.0, "?/ThreadpoolListener::StopRegion": 0.0,
        "?/ThreadpoolListener::Record": 0.0, "?/SlinkyThreadPool::Await": 0.0004403110000000001,
        "?/dot_general.1": 0.001091488, "?/end: dot_general.1": 2.3840000000000004e-06,
        "?/wrapped_reduce-window": 0.000258405, "?/end: wrapped_reduce-window": 1.091e-06,
        "?/wrapped_reduce": 5.257000000000001e-06, "?/end: wrapped_reduce": 7.16e-07,
        "?/ThunkExecutor::Execute (wait for completion)": 7.970000000000001e-07,
    }
    assert s.gap_s == {
        "window": 1.6004e-05,
        "answer.count: PjRtCpuExecutable::ExecuteHelper": 0.0006276720000000001,
        "answer.count": 0.000305762, "answer.count: $time sleep": 0.060511929000000006,
        "window: $time sleep": 0.020290990000000002,
    }


def test_recorded_count_trace_holds_engine_spans():
    window, events, ops = spans.read(COUNT_TRACE)
    answers = [e for e in events if e[0] == "tc.engine.count"]
    assert len(answers) == recorder.ANSWERS
    for name, s0, e0, stats in answers:
        inside = [e for e in events if s0 <= e[1] and e[2] <= e0 and e[0] != name]
        # every phase once per answer, tagged with the answer's number
        for phase in PHASES:
            (ev,) = [e for e in inside if e[0] == f"tc.engine.{phase}"]
            assert ev[3]["call"] == stats["call"]
        (plan,) = [e for e in inside if e[0] == "tc.engine.plan"]
        (dispatch,) = [e for e in inside if e[0] == "tc.engine.dispatch"]
        chunks = [e for e in inside if e[0] == "tc.engine.chunk"]
        assert len(chunks) == plan[3]["chunks"] == dispatch[3]["chunks"] > 1
        assert all(dispatch[1] <= c[1] and c[2] <= dispatch[2] for c in chunks)
        assert {c[3]["width"] for c in chunks} == {16, 64}
        assert dispatch[3]["slots"] == sum(2 * c[3]["rows"] * c[3]["width"] for c in chunks)
    s = spans.reduce(window, events, ops)
    busy = trace.reduce(*trace.read(COUNT_TRACE, trace.cpu_select))
    assert s.window_s == busy.window_s
    assert sum(s.span_idle_s.values()) == pytest.approx(s.idle_s)
    assert s.idle_s == pytest.approx(busy.window_s - busy.busy_s)
    assert s.span_idle_s["none"] >= recorder.BETWEEN     # the sleep between answers


def _expected_fill():
    """Needed over gathered slots, from the engine's plan of the graph."""
    from repro.core.engine import PanelBackend, workload_from_csr

    csr = recorder.graph()
    deg = np.asarray(csr.out_degree, np.int64)
    src, dst = np.asarray(csr.src), np.asarray(csr.col)
    needed = int((deg[src] + deg[dst]).sum())
    plan = PanelBackend().plan(workload_from_csr(csr), recorder.BUDGET)
    slots = sum(2 * len(c.u) * c.width for c in plan.chunks)
    return needed, slots


def test_metrics_read_the_recorded_count(monkeypatch):
    import importlib

    monkeypatch.setattr(spans, "trace_path", lambda: COUNT_TRACE)
    spans._reduced.cache_clear()
    needed, slots = _expected_fill()
    summary = trace.reduce(*trace.read(COUNT_TRACE, trace.cpu_select))
    run = Run(setup_s=0.0, window_s=summary.window_s, latencies=[0.1] * recorder.ANSWERS,
              plan_s=[], device_kind="cpu", work={"intersection_bytes": 4 * needed},
              trace=summary)
    window, events, _ = spans.read(COUNT_TRACE)
    w0, w1 = window
    within = {}
    for name, s0, e0, _ in events:
        within[name] = within.get(name, 0) + (min(e0, w1) - max(s0, w0))
    want = {
        "host_copy_share": 100 * within["tc.engine.host_copy"] / (w1 - w0),
        "dispatch_share": 100 * within["tc.engine.dispatch"] / (w1 - w0),
        "fold_share": 100 * within["tc.engine.fold"] / (w1 - w0),
        "panel_fill_share": 100 * needed / slots,
    }
    got = {m: importlib.import_module(f"bench.metrics.{m}").read(run) for m in METRICS}
    assert got == pytest.approx(want, rel=1e-9)
    assert 0 < got["panel_fill_share"] <= 100
    # a run without a trace, or with another run's trace, reads nothing
    assert all(importlib.import_module(f"bench.metrics.{m}").read(
        Run(0.0, 1.0, [0.1], [], "cpu", {"intersection_bytes": 4})) is None for m in METRICS)
    other = Run(0.0, 1.0, [0.1], [], "cpu", {"intersection_bytes": 4},
                trace=trace.Summary(window_s=1.0, busy_s=0.5, op_s={}, gap_s={}))
    assert all(importlib.import_module(f"bench.metrics.{m}").read(other) is None
               for m in METRICS)
    spans._reduced.cache_clear()
