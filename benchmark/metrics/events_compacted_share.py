"""Of the cluster-windows of the last job in which a cluster had more slab
events due than one pass of the event chunk loop applies, the share that
finished in a lane tile of the batch's deep ones, outside the batch's loop, in
percent: the program's `events_compacted` over its `events_deep` (summed over
clusters, as `metrics_summary()` published them after the window). 100 says
every deep cluster took its passes in a tile of its own; under 100 says that
some cluster-windows ran theirs in the batch's loop: too few events past the
chunk for the move to pay, or more clusters deep at once than a tile holds
(a job's first window, in which every cluster creates its nodes).
Nothing to read where the program has no such counters (a commit before
PR 49, or a batch of one lane tile, which has nothing to choose) or no window
was deep."""

from benchmark.free_kernel_counts import program_counters


def read(run):
    counted = program_counters("events_deep", "events_compacted")
    if counted is None or not counted["events_deep"]:
        return None
    return 100.0 * counted["events_compacted"] / counted["events_deep"]
