"""Percent of the traced window in which the engine reads the resident
graph to the host before planning: its ``tc.engine.host_copy`` spans."""
from bench import spans


def read(run):
    return spans.window_share(run, "tc.engine.host_copy")
