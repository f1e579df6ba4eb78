"""Device time of the cluster autoscaler's scale-up and scale-down kernels
per simulated window."""


def read(run):
    windows = run.counters.get("windows_stepped")
    trace = run.trace
    if trace is None or not windows:
        return None
    if not (trace.kernel_events.get("ca_up") or trace.kernel_events.get("ca_down")):
        return None
    return (trace.kernel_s["ca_up"] + trace.kernel_s["ca_down"]) * 1e3 / windows
