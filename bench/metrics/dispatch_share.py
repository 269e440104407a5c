"""Percent of the traced window in which the engine enqueues the chunks'
copies and kernels: its ``tc.engine.dispatch`` spans."""
from bench import spans


def read(run):
    return spans.window_share(run, "tc.engine.dispatch")
