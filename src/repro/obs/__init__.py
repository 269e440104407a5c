"""repro.obs — spans, counters, and trace export for the triangle engine.

The observability layer the timing claims rest on (§V of the paper is
*all* timings).  Three pieces:

* :mod:`repro.obs.tracer` — hierarchical spans of host phases.  Each
  span is a ``jax.profiler.TraceAnnotation`` named ``tc.<name>`` (so a
  profiler trace shows it on the clock of the device operations, and
  device time comes from that trace), and an event of the active
  :class:`Tracer` when one is on.  Spans never block on the device.
* :mod:`repro.obs.counters` — process-wide counters/gauges (chunks
  launched, wedges planned, cache hits, capability fallbacks).
* :mod:`repro.obs.export` — Chrome trace-event JSON (Perfetto-viewable)
  and structured JSONL exporters, plus stdlib-only validators.

Typical CLI wiring::

    with obs.trace_to_file(args.trace, meta={"cli": "count"}):
        with obs.span("ingest", cat="io"):
            graph = ...
        tc.count(graph)          # engine emits nested spans itself

and in engine code, a host phase whose duration also feeds a timing::

    with obs.span("engine.plan", cat="engine") as sp:
        plan = backend.plan(work, budget)
        sp.set(chunks=plan.n_chunks)
    timings["plan"] = sp.seconds

Importing this package never imports jax (the stdlib-only CI jobs use
the validators); the profiler sink is used once jax has been imported.
"""
from .counters import (
    Counter,
    Gauge,
    MetricsRegistry,
    counter,
    gauge,
    registry,
)
from .counters import reset as reset_metrics
from .counters import snapshot as metrics_snapshot
from .export import (
    SCHEMA,
    env_fingerprint,
    to_chrome_trace,
    to_jsonl_records,
    trace_to_file,
    validate_chrome_trace,
    validate_jsonl_records,
    write_trace,
)
from .hist import N_BUCKETS, ConcurrentHistogram, Pow2Histogram, RollingHistogram
from .tracer import (
    PROFILER_PREFIX,
    Span,
    Tracer,
    active,
    enabled,
    span,
    start_tracing,
    stop_tracing,
    tracing,
)

__all__ = [
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "N_BUCKETS",
    "PROFILER_PREFIX",
    "Pow2Histogram",
    "ConcurrentHistogram",
    "RollingHistogram",
    "SCHEMA",
    "Span",
    "Tracer",
    "active",
    "counter",
    "enabled",
    "env_fingerprint",
    "gauge",
    "metrics_snapshot",
    "registry",
    "reset_metrics",
    "span",
    "start_tracing",
    "stop_tracing",
    "to_chrome_trace",
    "to_jsonl_records",
    "trace_to_file",
    "tracing",
    "validate_chrome_trace",
    "validate_jsonl_records",
    "write_trace",
]
