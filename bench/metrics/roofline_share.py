"""Percent of device-busy time that the intersection's own bytes need at
the chip's peak HBM bandwidth: 4 B x sum over oriented edges of
(d+(u) + d+(v)), summed over the window's answers on their graphs, over
the peak (``bench/peaks.json``), over busy seconds.  The same work
whatever gather, kernel or fusion carries it out (``bench/work.py``)."""
from bench.peaks import peaks


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    least_s = run.total("intersection_bytes") / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
