"""Percent of device-busy time spent in the per-edge support scatter:
the operations of the program's jitted ``support_scatter``, which a
device trace names ``jit_support_scatter/<op>``.  None where no such
operation ran."""

OPS = "jit_support_scatter/"


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    scatter = run.trace.seconds_matching(OPS)
    return 100.0 * scatter / run.trace.busy_s if scatter > 0 else None
