"""The program's own spans in a traced window.

The engine's ``repro.obs`` spans are profiler annotations named
``tc.<span>`` (``tc.engine.plan``, ``tc.engine.dispatch``, ...), with
their counters as numeric stats, on the thread that holds the
``bench.window`` annotation.  This module reduces them to:

- ``span_s``: seconds inside the window, by span name;
- ``span_idle_s``: device-idle seconds of the window, by the innermost
  ``tc.`` span around them (``none`` outside every span);
- ``span_stats``: each numeric stat, summed over the spans of one name
  that start inside the window.

Reading (``read``) and reducing (``reduce``) are apart, as in
``bench/trace.py``, so that the reduction can be checked on hand-made
events and on a small count recorded on the CPU
(``bench/tests/make_cpu_count_trace.py``).  A span is ``(name, start_ns,
end_ns, stats)``.

The metric readers call ``of(run)``.  It takes the newest trace under
the benchmark's cache, which is the one the run just wrote, checks that
its window is the one ``bench/trace.py`` reduced, reduces it once, and
logs the ``idle by span`` line to standard error.  A trace of a program
without such spans gives empty tables, and the readers return None.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import sys

from bench import trace

PREFIX = "tc."
OUTSIDE = "none"
TRACES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      ".bench_cache", "trace")


@dataclasses.dataclass
class Spans:
    window_s: float
    idle_s: float           # device-idle seconds in the window, mean over chips
    span_s: dict            # span name -> seconds inside the window
    span_idle_s: dict       # innermost span name, or "none" -> device-idle seconds
    span_stats: dict        # span name -> {stat: sum over its spans in the window}


def device_lines(plane: str, line: str) -> bool:
    """Device operations: a TPU plane's ``XLA Ops``, or XLA's CPU executor."""
    if plane.startswith("/device:TPU:"):
        return line == "XLA Ops"
    return trace.cpu_select(plane, line)


def read(path: str, select=device_lines) -> tuple:
    """``((w0, w1), spans, {device plane: [(start, end)]})`` of one
    ``.xplane.pb``: the ``bench.window``, the ``tc.`` spans of its
    thread, and the device operations of every plane that ran any."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    window, spans, ops = None, [], {}
    for plane in data.planes:
        for line in plane.lines:
            if select(plane.name, line.name):
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
                if evs:
                    ops.setdefault(plane.name, []).extend(evs)
                continue
            held, mine = None, []
            for ev in line.events:
                name = ev.name
                if name == trace.WINDOW:
                    held = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith(PREFIX):
                    mine.append((name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                 dict(ev.stats)))
            if held is not None:
                window = held
                spans += mine
    if window is None:
        raise ValueError(f"no {trace.WINDOW} annotation in {path}")
    return window, spans, ops


def _number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def reduce(window: tuple, spans: list, ops: dict) -> Spans:
    w0, w1 = window
    if not ops:
        raise ValueError("the trace holds no device operations")
    span_s = collections.Counter()
    span_stats = collections.defaultdict(collections.Counter)
    inside = []
    for name, s, e, stats in spans:
        a, b = max(s, w0), min(e, w1)
        if b > a:
            span_s[name] += (b - a) * 1e-9
            inside.append((name, a, b))
        if w0 <= s < w1:
            for k, v in stats.items():
                if _number(v):
                    span_stats[name][k] += v
    bounds = sorted({t for _, a, b in inside for t in (a, b) if w0 < t < w1})
    pieces = []
    for evs in ops.values():
        merged = trace._union([(None, max(s, w0), min(e, w1)) for s, e in evs
                               if e > w0 and s < w1])
        prev_end = w0
        for s, e in merged + [(w1, w1)]:
            if s > prev_end:
                cuts = bounds[bisect.bisect_right(bounds, prev_end):bisect.bisect_left(bounds, s)]
                pieces += zip([prev_end] + cuts, cuts + [s])
            prev_end = e
    pieces.sort()
    chips = len(ops)
    span_idle_s = collections.Counter()
    names = trace._innermost(inside, [(a + b) / 2 for a, b in pieces])
    for (a, b), name in zip(pieces, names):
        span_idle_s[name or OUTSIDE] += (b - a) * 1e-9 / chips
    return Spans(
        window_s=(w1 - w0) * 1e-9,
        idle_s=sum(b - a for a, b in pieces) * 1e-9 / chips,
        span_s=dict(span_s),
        span_idle_s=dict(span_idle_s),
        span_stats={n: dict(c) for n, c in span_stats.items()},
    )


def trace_path() -> str | None:
    """The newest profiler trace under the benchmark's cache, or None."""
    paths = glob.glob(os.path.join(TRACES, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _table(d: dict, scale: float = 1.0) -> str:
    return " ".join(f"{k}={v * scale}" for k, v in sorted(d.items(), key=lambda x: -x[1]))


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float) -> Spans:
    s = reduce(*read(path))
    log(f"idle by span (s, of {s.idle_s} s idle): {_table(s.span_idle_s)}")
    log(f"spans (% of window): {_table(s.span_s, 100.0 / s.window_s)}")
    log(f"span stats: {s.span_stats}")
    return s


def of(run) -> Spans | None:
    """The spans of the run's traced window, or None without one."""
    if run.trace is None:
        return None
    path = trace_path()
    if path is None:
        return None
    s = _reduced(path, os.path.getmtime(path))
    # the trace bench/trace.py reduced has this very window
    if abs(s.window_s - run.trace.window_s) > 1e-9:
        return None
    return s


def window_share(run, name: str) -> float | None:
    """Percent of the traced window inside the spans called ``name``."""
    s = of(run)
    if s is None or name not in s.span_s:
        return None
    return 100.0 * s.span_s[name] / run.trace.window_s
