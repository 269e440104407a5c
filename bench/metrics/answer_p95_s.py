"""Nearest-rank 95th percentile of the window's answer latencies, in seconds."""
import math


def read(run):
    if not run.latencies:
        return None
    ordered = sorted(run.latencies)
    return ordered[math.ceil(0.95 * len(ordered)) - 1]
