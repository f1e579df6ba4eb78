"""Device time of the event kernel (`fused_event_scatter`: a chunk of due
trace events, node crashes and recoveries among them, applied to the per-slot
accumulators) per simulated window, from its events' self time in the trace
(`trace.op_self_s`). Nothing where the trace holds no such event (the event
loop on its scatter path)."""

from benchmark.event_kernel_counts import kernel_seconds


def read(run):
    windows = run.counters.get("windows_stepped")
    seconds = kernel_seconds(run.trace) if run.trace is not None else None
    if seconds is None or not windows:
        return None
    return seconds * 1e3 / windows
