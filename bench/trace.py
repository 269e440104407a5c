"""Reduce a profiler trace of the measured window to device busy time,
time by device operation, and idle gaps named by what the host was doing.

Reading (``read``) and reducing (``reduce``) are apart, so that the
reduction can be checked on a small trace recorded on the CPU
(``bench/tests/test_trace.py``).  Events are ``(name, start_ns, end_ns)``.

- Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<k>`` plane of the chips used (``select``), each named
  ``<module>/<op>`` after the ``XLA Modules`` event that holds it.
- The window is the benchmark's ``bench.window`` host annotation.
- Busy time is the union of the operation intervals inside the window,
  averaged over the chips; the idle share is one minus busy over window.
- An idle gap is a stretch of the window in which no operation runs.  It
  is cut where a ``bench.*`` host annotation starts or ends, and each
  piece is named ``<annotation>: <host event>`` after the innermost
  benchmark annotation around its midpoint and the innermost other event
  of the same thread there (a Python frame such as ``$engine.py:700
  plan`` where the profiler traces Python, or a dispatch such as
  ``PjitFunction(f)``); pieces of one name are summed.
"""
from __future__ import annotations

import collections
import bisect
import dataclasses
import glob
import os

WINDOW = "bench.window"
PREFIX = "bench."


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # union of operations, mean over chips
    op_s: dict                    # "<module>/<op>" -> device seconds in the window
    gap_s: dict                   # gap name -> idle seconds in the window

    def seconds_matching(self, needle: str) -> float:
        """Device seconds of operations whose name holds ``needle``."""
        return sum(s for name, s in self.op_s.items() if needle in name)

    def top_ops(self, k: int = 10) -> list:
        return [[n, s] for n, s in sorted(self.op_s.items(), key=lambda x: -x[1])[:k]]

    def top_gaps(self, k: int = 10) -> list:
        return [[n, s] for n, s in sorted(self.gap_s.items(), key=lambda x: -x[1])[:k]]


def tpu_select(chips: int):
    """The device-op lines of the first ``chips`` TPU planes."""
    planes = {f"/device:TPU:{k}" for k in range(chips)}
    return lambda plane, line: plane in planes and line == "XLA Ops"


def cpu_select(plane: str, line: str) -> bool:
    """XLA's CPU executor lines: the device ops of a trace recorded on the CPU."""
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def xplane_path(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _op_name(module: str, hlo: str) -> str:
    """``jit_f(123)``, ``%fusion.4 = s32[...] fusion(...)`` -> ``jit_f/fusion.4``."""
    return f"{module.split('(')[0]}/{hlo.split(' = ')[0].lstrip('%')}"


def read(path: str, select) -> tuple[list, dict]:
    """``(host events, {device plane: ops})`` of one ``.xplane.pb``.

    Host events are those of the thread that holds the ``bench.window``
    annotation: the benchmark's annotations, and the program's and
    Python's own events on that thread.  Each device's ops are named
    ``<module>/<op>`` (``_op_name``), the module being the
    ``XLA Modules`` event of the same plane that holds the op's start.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    host, ops = [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        chosen = [ln for ln in lines if select(plane.name, ln.name)]
        for line in lines:
            if line in chosen:
                continue
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
            if any(name == WINDOW for name, _, _ in evs):
                host += evs
        if not chosen:
            continue
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ln in lines if ln.name == "XLA Modules" for ev in ln.events)
        ops[plane.name] = [(_op_name(_module_at(modules, ev.start_ns), ev.name),
                            ev.start_ns, ev.start_ns + ev.duration_ns)
                           for line in chosen for ev in line.events]
    return host, ops


def _module_at(modules: list, t: float) -> str:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return "?"


def _union(intervals: list) -> list:
    """Merged ``(start, end)`` of ``(name, start, end)`` events."""
    merged = []
    for _, s, e in sorted(intervals, key=lambda x: (x[1], x[2])):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def _innermost(events: list, times: list) -> list:
    """The innermost event around each of the sorted ``times``, or None.

    The events of one thread nest, so a stack of the open ones, swept in
    time order, holds the innermost on top.
    """
    events = sorted(events, key=lambda x: (x[1], -x[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][1] <= t:
            while stack and stack[-1][2] < events[i][1]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def reduce(host: list, ops: dict) -> Summary:
    """Busy time, op time and named idle gaps inside the ``bench.window``."""
    ours = [h for h in host if h[0].startswith(PREFIX)]
    theirs = [h for h in host if not h[0].startswith(PREFIX)]
    windows = [(s, e) for n, s, e in ours if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(windows)}")
    w0, w1 = windows[0]
    if not ops:
        raise ValueError("the trace holds no device operations")
    bounds = sorted({t for _, s, e in ours for t in (s, e) if w0 < t < w1})
    op_s = collections.Counter()
    pieces = []                      # (start, end) of idle stretches, all chips
    busy = 0.0
    for evs in ops.values():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]
        for n, s, e in inside:
            op_s[n] += (e - s) * 1e-9
        merged = _union(inside)
        busy += sum(e - s for s, e in merged) * 1e-9
        prev_end = w0
        for s, e in merged + [(w1, w1)]:
            if s > prev_end:
                cuts = bounds[bisect.bisect_right(bounds, prev_end):bisect.bisect_left(bounds, s)]
                pieces += zip([prev_end] + cuts, cuts + [s])
            prev_end = e
    pieces.sort()
    mids = [(a + b) / 2 for a, b in pieces]
    gap_s = collections.Counter()
    for (a, b), ann, frame in zip(pieces, _innermost(ours, mids), _innermost(theirs, mids)):
        name = ann[len(PREFIX):] if ann else "outside"
        gap_s[f"{name}: {frame}" if frame else name] += (b - a) * 1e-9
    chips = len(ops)
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy / chips,
        op_s={n: s / chips for n, s in op_s.items()},
        gap_s={n: s / chips for n, s in gap_s.items()},
    )
