"""Device time of the scheduling-cycle kernel per simulated window."""


def read(run):
    windows = run.counters.get("windows_stepped")
    if run.trace is None or not windows or not run.trace.kernel_events.get("cycle"):
        return None
    return run.trace.kernel_s["cycle"] * 1e3 / windows
