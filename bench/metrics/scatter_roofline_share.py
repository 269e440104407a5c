"""Percent of the support scatter's device time that its least bytes
need at the chip's peak HBM bandwidth: the int32 ``arm`` and ``closure``
values it must at least read, 4 B x sum over oriented edges of
(d+(u) + d+(v)) (``bench/work.py``), summed over the window's answers on
their graphs, over the peak (``bench/peaks.json``), over the device
seconds of the ``jit_support_scatter/`` operations.  None where no such
operation ran."""
from bench.metrics.scatter_share import OPS
from bench.peaks import peaks


def read(run):
    if run.trace is None:
        return None
    scatter = run.trace.seconds_matching(OPS)
    if scatter <= 0:
        return None
    least_s = run.total("intersection_bytes") / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / scatter
