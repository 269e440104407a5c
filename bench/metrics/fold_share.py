"""Percent of the traced window in which the engine copies the ready
partials to the host and sums them: its ``tc.engine.fold`` spans."""
from bench import spans


def read(run):
    return spans.window_share(run, "tc.engine.fold")
