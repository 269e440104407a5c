"""Runtime audit layer: the ``REPRO_CHECK=1`` sanitizer and CompileAuditor.

Static passes (``python -m repro.check``) catch patterns; this module
checks the two invariants that only hold *dynamically*:

* **int32 partial headroom** — with ``REPRO_CHECK=1`` in the environment,
  ``run_workload`` routes every device partial through
  :func:`check_partial` before the host fold, asserting it is a narrow
  integer (int32-or-smaller, the device accumulator contract) whose values
  retain headroom below 2^30.  A partial at 2^30 means one more doubling
  overflows int32 *on device*, before any host fold can widen it.
* **O(log m) compilations** — :class:`CompileAuditor` snapshots the jit
  trace-cache sizes of the engine's kernel entry points around a workload
  and asserts no kernel traced more than O(log m) new shapes (the pow2
  bucketing guarantee behind truss peeling and incremental sessions).

Overhead of the sanitizer is a device->host sync per chunk (min/max of the
partial); see EXPERIMENTS.md for the measured cost on the kron-13 count.
"""

from __future__ import annotations

import math
import os

import numpy as np

REPRO_CHECK_ENV = "REPRO_CHECK"

# Values at/above this lack doubling headroom inside int32.
PARTIAL_HEADROOM = 1 << 30


class RuntimeCheckError(AssertionError):
    """An engine correctness invariant failed at runtime."""


def enabled() -> bool:
    """True when the ``REPRO_CHECK`` env var is set to a truthy value."""
    return os.environ.get(REPRO_CHECK_ENV, "").strip().lower() not in (
        "", "0", "false", "off", "no",
    )


def check_partial(part, *, kind: str, context: str = "") -> None:
    """Assert one device partial honors the int32-accumulator contract.

    ``part`` is whatever a backend's ``count_chunk`` / ``per_node_chunk``
    / ``support_chunk`` returned, *before* the host fold widens it.
    """
    a = np.asarray(part)
    where = f" ({context})" if context else ""
    if a.size == 0:
        return
    if a.dtype.kind == "b":
        return
    if a.dtype.kind not in "iu":
        raise RuntimeCheckError(
            f"REPRO_CHECK: {kind} partial{where} has non-integer dtype {a.dtype}; "
            "device kernels must emit integer counts"
        )
    if a.dtype.itemsize > 4:
        raise RuntimeCheckError(
            f"REPRO_CHECK: {kind} partial{where} arrived as {a.dtype}; the device "
            "accumulator contract is int32 — a 64-bit device dtype hides exactly "
            "the overflow the host fold exists to absorb"
        )
    lo = int(a.min())
    hi = int(a.max())
    if lo < 0:
        raise RuntimeCheckError(
            f"REPRO_CHECK: {kind} partial{where} contains negative count {lo}; "
            "likely an int32 wraparound on device"
        )
    if hi >= PARTIAL_HEADROOM:
        raise RuntimeCheckError(
            f"REPRO_CHECK: {kind} partial{where} peaks at {hi} >= 2^30; no "
            "doubling headroom left in the int32 device accumulator — shrink "
            "the chunk budget"
        )


def check_partials(partials, *, kind: str, context: str = "") -> None:
    for i, p in enumerate(partials):
        check_partial(p, kind=kind, context=context or f"chunk {i}")


# ---------------------------------------------------------------------------
# CompileAuditor


def _default_kernel_table():
    """Name -> jitted fn for the repo's kernel entry points (lazy imports)."""
    from repro.core import count as _count
    from repro.core import engine as _engine

    jitted = {
        "chunk_count_kernel": _engine.chunk_count_kernel,
        "chunk_per_node_kernel": _engine.chunk_per_node_kernel,
        "chunk_support_kernel": _engine.chunk_support_kernel,
        "gather_panels": _count.gather_panels,
        "gather_panels_arrays": _count.gather_panels_arrays,
    }
    try:
        from repro.kernels.triangle_count import triangle_count as _tc

        jitted["pallas_run"] = _tc._run
    except Exception:  # pallas layer optional at audit time
        pass
    lru = {}
    try:
        from repro.core import distributed as _dist

        lru["striped_workload_fn"] = _dist.striped_workload_fn
    except Exception:
        pass
    return jitted, lru


class CompileAuditor:
    """Counts actual jit tracings per kernel across a ``with`` block.

    Uses the trace-cache sizes jax maintains per jitted callable (and
    ``lru_cache`` stats for the striped shard_map factory), so it measures
    *real* compilations, not estimates.  ``assert_log_bound(m)`` then
    enforces the engine's O(log m) promise: with pow2 bucketing, a full
    truss decomposition or incremental session over an m-edge graph may
    trace at most ``factor * log2(m) + slack`` distinct shapes per kernel.
    """

    def __init__(self, extra_jitted=None):
        self._jitted, self._lru = _default_kernel_table()
        if extra_jitted:
            self._jitted.update(extra_jitted)
        self._start = None
        self._end = None

    def _snapshot(self) -> "dict[str, int]":
        sizes: "dict[str, int]" = {}
        for name, fn in self._jitted.items():
            try:
                sizes[name] = int(fn._cache_size())
            except Exception:
                sizes[name] = 0
        for name, fn in self._lru.items():
            sizes[name] = int(fn.cache_info().currsize)
        return sizes

    def __enter__(self) -> "CompileAuditor":
        self._start = self._snapshot()
        self._end = None
        return self

    def __exit__(self, *exc) -> bool:
        self._end = self._snapshot()
        return False

    @property
    def new_traces(self) -> "dict[str, int]":
        """Per-kernel count of traces minted inside the block."""
        if self._start is None:
            raise RuntimeCheckError("CompileAuditor used outside a with block")
        end = self._end if self._end is not None else self._snapshot()
        return {
            name: max(0, end.get(name, 0) - self._start.get(name, 0))
            for name in end
        }

    @property
    def total_new_traces(self) -> int:
        return sum(self.new_traces.values())

    def assert_log_bound(self, m: int, *, factor: float = 4.0, slack: int = 6) -> int:
        """Assert every kernel traced <= ``factor*log2(m) + slack`` shapes.

        Returns the bound so callers can log it.  ``factor`` covers the
        independent static axes that legitimately multiply the shape
        buckets (wedge budget x bisection depth), ``slack`` the one-off
        warmup shapes.
        """
        bound = int(factor * math.log2(max(int(m), 2)) + slack)
        offenders = {k: v for k, v in self.new_traces.items() if v > bound}
        if offenders:
            raise RuntimeCheckError(
                f"REPRO_CHECK: compile-count bound exceeded for m={m} "
                f"(bound {bound}): {offenders}; pow2 bucketing is not reaching "
                "these kernels (see trilint pass `recompile`)"
            )
        return bound
