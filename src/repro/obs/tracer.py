"""Hierarchical span tracer for the triangle engine.

The paper's claims are *timings* (§V: 8–15× over CPU, 3.8B triangles in
under 10 s), so the repo needs a way to attribute a run's wall clock to
its phases.  This module is the core of that layer: one context-manager
span API with two sinks.

* **The profiler's timeline.**  Every span enters a
  ``jax.profiler.TraceAnnotation`` named ``tc.<span name>`` whose stats
  are the span's integer and float args, whether or not a
  :class:`Tracer` is active.  Under ``jax.profiler.trace`` the spans so
  land in the device trace on the same clock as the device operations,
  and an idle stretch of the device can be put down to the host phase
  around it.  Outside a profiler trace an annotation costs about a
  microsecond.
* **The tracer's event list.**  Under an active :class:`Tracer` the same
  span is also recorded as a plain dict (``name``/``cat``/``ts_ns``/
  ``dur_ns``/``depth``/``args``) relative to the tracer's origin, ready
  for the Chrome trace-event / JSONL exporters in :mod:`repro.obs.export`.

A span times the host: JAX dispatches kernels asynchronously, so a span
around a launch measures the enqueue, and device time comes from the
device trace.  Spans never block on the device — a traced run does the
same work as an untraced one.  The trilint ``obs_discipline`` pass
holds spans over device work to this: such a span is named as a
dispatch (``.dispatch``/``.chunk``) or blocks on its result itself.
A span's own clock reads are exposed (:attr:`Span.seconds`), so
callers derive phase timings from the very reads that bound the span.

``jax`` is never imported here: the profiler sink is used once the
process has imported jax, since no profiler trace can run before that.
Exporters and validators so stay usable in jax-free contexts.

A tracer also runs a :class:`repro.check.runtime.CompileAuditor` for its
lifetime, so every exported trace reports how many jit traces the run
minted per kernel.
"""
from __future__ import annotations

import contextlib
import numbers
import sys
import time

__all__ = [
    "PROFILER_PREFIX",
    "Span",
    "Tracer",
    "active",
    "enabled",
    "span",
    "start_tracing",
    "stop_tracing",
    "tracing",
]

PROFILER_PREFIX = "tc."  # a span's name on the profiler's timeline

_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported


def _annotation_class():
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def _stats(args: dict) -> dict:
    """The integer and float args, as profiler stats."""
    out = {}
    for k, v in args.items():
        t = type(v)
        if t is int or t is float:
            out[k] = v
        elif t is bool:
            continue
        elif isinstance(v, numbers.Integral):
            out[k] = int(v)
        elif isinstance(v, numbers.Real):
            out[k] = float(v)
    return out


class Span:
    """One span (context manager): a profiler annotation, and an event of
    the active :class:`Tracer` if there is one.

    Records its event on ``__exit__`` even when the body raises (the
    event then carries an ``error`` key) — a crash mid-phase still
    leaves a closed, exportable span.  After the span closes,
    :attr:`seconds` is its duration from the same two clock reads that
    bound its event.
    """

    __slots__ = ("_tracer", "name", "cat", "args", "t0_ns", "t1_ns", "_depth", "_ann")

    def __init__(self, tracer: "Tracer | None", name: str, cat: str, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = dict(args) if args else None
        self.t0_ns = self.t1_ns = 0
        self._depth = 0
        self._ann = None

    def __enter__(self) -> "Span":
        cls = _annotation_class()
        if cls is not None:
            self._ann = cls(PROFILER_PREFIX + self.name)
            self._ann.__enter__()
        t = self._tracer
        if t is not None:
            self._depth = t._depth
            t._depth += 1
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        ann = self._ann
        if ann is not None and self.args:
            ann.set_metadata(**_stats(self.args))
        self.t1_ns = time.perf_counter_ns()
        if ann is not None:
            ann.__exit__(exc_type, exc, tb)
        t = self._tracer
        if t is None:
            return False
        t._depth = self._depth
        event = {
            "name": self.name,
            "cat": self.cat,
            "ts_ns": self.t0_ns - t._origin_ns,
            "dur_ns": self.t1_ns - self.t0_ns,
            "depth": self._depth,
        }
        if self.args:
            event["args"] = self.args
        if exc_type is not None:
            event["error"] = exc_type.__name__
        t.events.append(event)
        return False

    @property
    def seconds(self) -> float:
        """Seconds between the span's opening and closing clock reads."""
        return (self.t1_ns - self.t0_ns) / 1e9

    def set(self, **kwargs) -> "Span":
        """Attach/overwrite args (exported, and the numeric ones become
        profiler stats when the span closes)."""
        if self.args is None:
            self.args = {}
        self.args.update(kwargs)
        return self


class Tracer:
    """Collects span events (and jit-trace counts) for one traced region.

    Not thread-safe — the engine is single-threaded host-side, and a
    tracer's span stack is per-process state exactly like the engine's
    ``last_stats``.
    """

    def __init__(self, *, audit_compiles: bool = True):
        self.events: list[dict] = []
        self.meta: dict = {}
        self.jit_traces: dict[str, int] = {}
        self._origin_ns = time.perf_counter_ns()
        self._depth = 0
        self._audit_compiles = audit_compiles
        self._auditor = None

    def span(self, name: str, cat: str = "", args=None) -> Span:
        return Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "", args=None) -> None:
        """Record a zero-duration marker event."""
        event = {
            "name": name,
            "cat": cat,
            "ts_ns": time.perf_counter_ns() - self._origin_ns,
            "dur_ns": 0,
            "depth": self._depth,
        }
        if args:
            event["args"] = dict(args)
        self.events.append(event)

    def wall_s(self) -> float:
        """Seconds from the tracer's origin to now (or to the last event)."""
        return (time.perf_counter_ns() - self._origin_ns) / 1e9

    # -- lifecycle (driven by start_tracing/stop_tracing) -------------------

    def _start(self) -> None:
        if self._audit_compiles:
            try:
                from repro.check.runtime import CompileAuditor

                self._auditor = CompileAuditor()
                self._auditor.__enter__()
            except Exception:  # jax unavailable: tracer still works, no audit
                self._auditor = None
        # re-anchor after the auditor's (possibly first) jax import, so the
        # first span doesn't inherit the import cost as leading dead time
        self._origin_ns = time.perf_counter_ns()

    def _finish(self) -> None:
        if self._auditor is None:
            return
        auditor, self._auditor = self._auditor, None
        auditor.__exit__(None, None, None)
        self.jit_traces = {k: v for k, v in auditor.new_traces.items() if v}


# -- module-level switchboard ------------------------------------------------
#
# One active tracer per process, mirroring how the engine's stats and
# fallback warnings are process-global.  With no tracer a span is only
# its profiler annotation and its two clock reads.

_ACTIVE: Tracer | None = None


def active() -> Tracer | None:
    """The active tracer, or None when tracing is disabled."""
    return _ACTIVE


def enabled() -> bool:
    return _ACTIVE is not None


def span(name: str, cat: str = "", args=None) -> Span:
    """A span: on the profiler's timeline always, on the active tracer
    when there is one."""
    return Span(_ACTIVE, name, cat, args)


def start_tracing(tracer: Tracer | None = None) -> Tracer:
    """Install (and start) the process-wide tracer.

    Nested tracing is rejected loudly: two tracers would silently split
    the event stream, and every caller here owns a whole CLI run.
    """
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError("tracing is already active; stop_tracing() first")
    t = tracer if tracer is not None else Tracer()
    t._start()
    _ACTIVE = t
    return t


def stop_tracing() -> Tracer | None:
    """Uninstall the active tracer (folding in jit-trace counts)."""
    global _ACTIVE
    t, _ACTIVE = _ACTIVE, None
    if t is not None:
        t._finish()
    return t


@contextlib.contextmanager
def tracing(tracer: Tracer | None = None):
    """``with obs.tracing() as t:`` — scoped start/stop."""
    t = start_tracing(tracer)
    try:
        yield t
    finally:
        stop_tracing()
