"""Of the cluster-windows of the last job in which the reschedule order ranked
a removed node's pods, the share in which the cluster had more of them than
the compacted rank holds, in percent: the program's `resched_rank_sorted` over
its `resched_rank_windows` (summed over clusters, as `metrics_summary()`
published them after the window). Any such cluster sends its window of the
batch to the sort of the whole pod axis, so 0 says that no window of the job
sorted. Nothing to read where the program has no such counters (a commit
before PR 44) or ranked in no window."""

from benchmark.free_kernel_counts import program_counters


def read(run):
    counted = program_counters("resched_rank_windows", "resched_rank_sorted")
    if counted is None or not counted["resched_rank_windows"]:
        return None
    return 100.0 * counted["resched_rank_sorted"] / counted["resched_rank_windows"]
