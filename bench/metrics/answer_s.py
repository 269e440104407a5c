"""Seconds per answer: the window's length over the answers it completed."""


def read(run):
    return run.window_s / len(run.latencies) if run.latencies else None
