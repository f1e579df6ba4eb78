"""Device time of the free kernel (`fused_free_resources`: freed pods' requests
back to their nodes' allocatable, through the pending-free channel where the
control plane has delays) per simulated window, from its events' self time in
the trace (`trace.op_self_s`)."""

from benchmark.free_kernel_counts import kernel_seconds


def read(run):
    windows = run.counters.get("windows_stepped")
    seconds = kernel_seconds(run.trace) if run.trace is not None else None
    if seconds is None or not windows:
        return None
    return seconds * 1e3 / windows
