"""Percent of the answers' wall time spent in the engine's host planning,
``EngineStats.timings["plan"]`` summed over the answers of the window."""


def read(run):
    if not run.plan_s or not run.latencies:
        return None
    return 100.0 * sum(run.plan_s) / sum(run.latencies)
