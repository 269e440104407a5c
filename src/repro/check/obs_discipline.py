"""trilint pass: observability spans over device work say what they time.

The PR 8 bug class: JAX dispatch is asynchronous, so a span that wraps a
kernel launch records the *enqueue* (microseconds), not the device
compute — a span passed off as device time makes the trace look
implausibly fast and every number derived from it garbage.  Spans never
block on the device by themselves (a traced run does the same work as an
untraced one); device time comes from the profiler's trace.  The
invariant: any ``with ...span(...)`` block whose body launches device
work either is a dispatch span — its name ends in ``.dispatch`` or
``.chunk``, so it says it times the enqueue — or blocks on the result
itself with ``jax.block_until_ready`` before it closes.

* ``D1-unsynced-span`` — a span context manager, not named as a
  dispatch, whose body calls a device-work entry point but never
  ``block_until_ready``.

"Device work" is recognized by call-name convention, matching the
engine's kernel vocabulary: a last dotted segment that starts with
``chunk_`` or ``intersect_``, ends with ``_chunk``, or is one of the
known launch wrappers (``pallas_call``, ``shard_map``,
``striped_workload_fn``).  Spans around pure-host work (parsing, CSR
assembly, numpy folds) are exempt — host calls return only when done, so
the span is honest without a block.
"""

from __future__ import annotations

import ast

from .base import Finding, ModuleInfo, call_name, register_pass, walk_calls

# Launch wrappers that dispatch device work without the kernel naming
# convention (kept in sync with repro.kernels / repro.distributed).
LAUNCH_WRAPPERS = frozenset({"pallas_call", "shard_map", "striped_workload_fn"})

# Call names that prove the span waited for the device.
SYNC_NAMES = frozenset({"block_until_ready"})

# Span-name endings that declare a span times the enqueue of device work.
DISPATCH_SUFFIXES = (".dispatch", ".chunk")


def _is_span_call(node: ast.expr) -> bool:
    """True for ``obs.span(...)`` / ``trc.span(...)`` / ``tracer.span(...)``."""
    if not isinstance(node, ast.Call):
        return False
    name = call_name(node)
    return name == "span" or name.endswith(".span")


def _is_dispatch_span(node: ast.Call) -> bool:
    """True when the span's name (a literal, or an f-string's literal
    tail) ends in a dispatch suffix."""
    if not node.args:
        return False
    name = node.args[0]
    if isinstance(name, ast.JoinedStr) and name.values:
        name = name.values[-1]
    return (
        isinstance(name, ast.Constant)
        and isinstance(name.value, str)
        and name.value.endswith(DISPATCH_SUFFIXES)
    )


def _is_device_work(name: str) -> bool:
    last = name.rsplit(".", 1)[-1]
    return (
        last.startswith("chunk_")
        or last.startswith("intersect_")
        or last.endswith("_chunk")
        or last in LAUNCH_WRAPPERS
    )


@register_pass("obs_discipline")
def check_obs_discipline(mod: ModuleInfo) -> "list[Finding]":
    findings: "list[Finding]" = []

    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        spans = [item.context_expr for item in node.items
                 if _is_span_call(item.context_expr)]
        if not spans or any(_is_dispatch_span(sp) for sp in spans):
            continue

        device_calls: "list[str]" = []
        synced = False
        for call in walk_calls(ast.Module(body=node.body, type_ignores=[])):
            name = call_name(call)
            if not name:
                continue
            if name.rsplit(".", 1)[-1] in SYNC_NAMES:
                synced = True
            elif _is_device_work(name):
                device_calls.append(name)

        if device_calls and not synced:
            launches = ", ".join(sorted(set(device_calls)))
            findings.append(
                mod.finding(
                    "obs_discipline",
                    "D1-unsynced-span",
                    node,
                    f"span wraps device work ({launches}) but is not named as "
                    "a dispatch and never blocks; JAX dispatch is async, so "
                    "the span records enqueue latency, not device time — name "
                    "it `<...>.dispatch`/`<...>.chunk`, or call "
                    "`jax.block_until_ready` on the result before it exits",
                )
            )
    return findings
