"""Median wall time of one fleet.pump() round inside the window."""

from benchmark.spans import median


def read(run):
    rounds = run.spans.durations("pump")
    return median(rounds) * 1e3 if rounds else None
