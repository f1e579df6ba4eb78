"""Of the traced window's device-busy time, the percent no phase claims: ops
without an `op_name` (layout copies), ops whose scope path names no device
phase, ops of a program nobody noted, and names two programs put under
different phases (benchmark/phase_times.py). None where the program has no
map."""

from benchmark import phase_times


def read(run):
    times = phase_times.read(run)
    if times is None or not run.trace.busy_s:
        return None
    return 100.0 * times.unscoped_s / run.trace.busy_s
