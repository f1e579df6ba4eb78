"""Device time of the candidate scheduling kernel (`fused_schedule_cycle`, the
formulation under 128 clusters a shard) per simulated window, from its events'
self time in the trace (`trace.op_self_s`)."""

from benchmark.candidate_kernel_counts import kernel_seconds


def read(run):
    windows = run.counters.get("windows_stepped")
    seconds = kernel_seconds(run.trace) if run.trace is not None else None
    if seconds is None or not windows:
        return None
    return seconds * 1e3 / windows
