"""Of the cluster-cycles of the last job whose queue was deeper than one pass,
the share that the megakernel's second launch drained, the cluster brought
into a lane tile of the batch's deep ones, in percent: the program's
`cycle_compacted` over its `cycle_deep` (summed over clusters, as
`metrics_summary()` published them after the window). 100 says every deep
cycle ran in a tile of its own; under 100 says that in some cycle more
clusters were deep than a tile holds, or the move would not have paid (a deep
cluster alone, a few lanes just past a pass), or the batch is one tile.
Nothing to read where the program
has no such counters (a commit before PR 45) or no cycle was deep."""

from benchmark.free_kernel_counts import program_counters


def read(run):
    counted = program_counters("cycle_deep", "cycle_compacted")
    if counted is None or not counted["cycle_deep"]:
        return None
    return 100.0 * counted["cycle_compacted"] / counted["cycle_deep"]
