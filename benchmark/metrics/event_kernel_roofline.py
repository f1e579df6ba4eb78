"""The event kernel's share of its roofline: the least time the chip could
take for its launches in the traced window, over the time they took. Says
which bound on a `roofline` line. The launches are the program's own count of
the windows in which event application was due, the largest of any cluster, a
job, as `free_kernel_roofline` counts its own (never more than ran); a
launch's steps are the mean events a cluster applied a launch, which the
driver counts off the traces it compiled (`events_per_cluster`). Left out
where the driver or the program counts neither."""

from benchmark import event_kernel_counts, free_kernel_counts, kernel_counts, peaks
from benchmark.harness import say


def read(run):
    c = run.counters
    seconds = event_kernel_counts.kernel_seconds(run.trace) if run.trace is not None else None
    counted = free_kernel_counts.program_counters("event_windows")
    needed = ("jobs", "events_per_cluster", "event_chunk")
    if seconds is None or counted is None or not counted["event_windows"] or not all(c.get(k) for k in needed):
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips  # one chip's shard, as the trace is averaged
    faults = bool(c.get("node_faults"))
    launches = counted["event_windows"] * c["jobs"]
    steps = c["events_per_cluster"] / counted["event_windows"]
    hbm = event_kernel_counts.event_hbm_bytes(clusters, c["nodes"], c["pods"], c["event_chunk"], faults)
    ops = event_kernel_counts.event_ops(clusters, c["nodes"], c["pods"], steps, faults)
    least = kernel_counts.roofline(hbm, ops, peak)
    say(line="roofline", kernel="event", bound=least["bound"], launches=launches, steps_per_launch=steps,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"])
    return 100.0 * least["least_s"] * launches / seconds
