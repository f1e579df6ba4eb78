"""Device-busy milliseconds per lane-window dispatched by the fleet."""


def read(run):
    lane_windows = run.counters.get("lane_windows")
    if run.trace is None or not lane_windows:
        return None
    return run.trace.busy_s * 1e3 / lane_windows
