"""Device-busy milliseconds per simulated window (all clusters in lockstep):
busy time of the traced window over the windows its jobs stepped."""


def read(run):
    windows = run.counters.get("windows_stepped")
    if run.trace is None or not windows:
        return None
    return run.trace.busy_s * 1e3 / windows
