"""The free kernel's share of its roofline: the least time the chip could take
for its launches in the traced window, over the time they took. Says which
bound on a `roofline` line. The launches are the program's own count of the
windows in which event application was due, the largest of any cluster, a job
(never more than ran); where the program does not count them (a commit before
PR 32) the metric is left out."""

from benchmark import free_kernel_counts, kernel_counts, peaks
from benchmark.harness import say


def read(run):
    c = run.counters
    seconds = free_kernel_counts.kernel_seconds(run.trace) if run.trace is not None else None
    counted = free_kernel_counts.program_counters("event_windows", "frees_total")
    if seconds is None or counted is None or not counted["event_windows"] or not c.get("jobs"):
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips  # one chip's shard, as the trace is averaged
    launches = counted["event_windows"] * c["jobs"]
    steps = counted["frees_total"] / c["clusters"] / counted["event_windows"]
    hbm = free_kernel_counts.free_hbm_bytes(clusters, c["nodes"], c["pods"])
    ops = free_kernel_counts.free_ops(clusters, c["nodes"], c["pods"], steps)
    least = kernel_counts.roofline(hbm, ops, peak)
    say(line="roofline", kernel="free", bound=least["bound"], launches=launches,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"])
    return 100.0 * least["least_s"] * launches / seconds
