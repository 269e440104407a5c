"""The trace reduction, on hand-made events and on a trace recorded on
the CPU (``make_cpu_trace.py``)."""
import os

import numpy as np
import pytest

from bench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data", "cpu_window.xplane.pb")


def test_reduce_hand_made_events():
    host = [("bench.window", 0, 100), ("bench.answer.count", 5, 50),
            ("bench.answer.count", 55, 100), ("PjitFunction(f)", 0, 100),
            ("$engine.py:700 plan", 38, 62)]
    ops = {"/device:TPU:0": [("m/a", 10, 30), ("m/b", 20, 40), ("m/c", 60, 70),
                             ("m/d", 95, 120), ("m/before", -20, -10)]}
    s = trace.reduce(host, ops)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)            # [10,40] + [60,70] + [95,100]
    assert s.op_s == pytest.approx({"m/a": 20e-9, "m/b": 20e-9, "m/c": 10e-9, "m/d": 5e-9})
    # idle [0,10], [40,60], [70,95], cut where an answer starts or ends
    assert s.gap_s == pytest.approx({
        "window: PjitFunction(f)": 5e-9,                 # [0,5]
        "answer.count: PjitFunction(f)": 30e-9,          # [5,10] and [70,95]
        "answer.count: $engine.py:700 plan": 15e-9,      # [40,50] and [55,60]
        "window: $engine.py:700 plan": 5e-9,             # [50,55]
    })
    assert s.busy_s + sum(s.gap_s.values()) == pytest.approx(s.window_s)


def test_op_names():
    assert trace._op_name("jit__run(1749)", "%intersect_count.1 = s32[1,16384] custom-call("
                          "s32[1024,16384] %fusion)") == "jit__run/intersect_count.1"
    assert trace._op_name("?", "dot_general.1") == "?/dot_general.1"


def test_reduce_averages_chips():
    host = [("bench.window", 0, 100)]
    ops = {"/device:TPU:0": [("m/a", 0, 100)], "/device:TPU:1": [("m/a", 0, 50)]}
    s = trace.reduce(host, ops)
    assert s.busy_s == pytest.approx(75e-9)
    assert s.gap_s == pytest.approx({"window": 25e-9})


def test_reduce_needs_one_window_and_some_ops():
    with pytest.raises(ValueError):
        trace.reduce([], {"/device:TPU:0": [("m/a", 0, 1)]})
    with pytest.raises(ValueError):
        trace.reduce([("bench.window", 0, 1)], {})


def test_tpu_select():
    sel = trace.tpu_select(1)
    assert sel("/device:TPU:0", "XLA Ops")
    assert not sel("/device:TPU:1", "XLA Ops")
    assert not sel("/device:TPU:0", "XLA Modules")


def union_by_timeline(evs, w0, w1):
    """Busy nanoseconds by marking every nanosecond: an independent union."""
    busy = np.zeros(int(w1 - w0), bool)
    for _, s, e in evs:
        busy[int(max(s, w0) - w0):int(max(min(e, w1), w0) - w0)] = True
    return int(busy.sum())


def test_reduce_recorded_cpu_trace():
    host, ops = trace.read(RECORDED, trace.cpu_select)
    names = [n for n, _, _ in host]
    assert names.count("bench.window") == 1 and names.count("bench.answer.count") == 3
    s = trace.reduce(host, ops)
    (w0, w1), = [(a, b) for n, a, b in host if n == "bench.window"]
    evs = [e for line in ops.values() for e in line]
    assert s.busy_s * 1e9 == pytest.approx(union_by_timeline(evs, w0, w1), abs=len(evs))
    assert s.busy_s + sum(s.gap_s.values()) == pytest.approx(s.window_s)
    # three 20 ms sleeps inside answers, two 10 ms sleeps between them
    inside = sum(v for k, v in s.gap_s.items() if k.startswith("answer.count"))
    between = sum(v for k, v in s.gap_s.items() if k.startswith("window"))
    assert inside >= 3 * 0.020 and between >= 2 * 0.010
    assert inside + between == pytest.approx(sum(s.gap_s.values()))
    # the sleeps are named after the Python frame the host was in
    assert s.gap_s["answer.count: $time sleep"] >= 3 * 0.020
    assert s.seconds_matching("dot") > 0      # the matrix product, found by name
    assert 0 < s.busy_s < s.window_s
