"""Percent of device-busy time spent in the Pallas intersection kernels,
the operations named after ``pallas_call(name="intersect_<kind>")``."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    kernel = run.trace.seconds_matching("intersect_")
    return 100.0 * kernel / run.trace.busy_s if kernel > 0 else None
