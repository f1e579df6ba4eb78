"""Of the last job's attempts to place a pod that carries a nodeSelector, a
node affinity or a toleration, the share that ended unschedulable although a
live node had room, in percent: the program's `affinity_attempts_refused`
over its `affinity_attempts`, as `metrics_summary()` published them after the
window (the driver copies them into its counters). How often a pinned pod
found its pool full in a cluster that had room elsewhere: the load the cell
offers, 0 where no pool ever filled, and nothing to read where the program has
no such counters."""


def read(run):
    attempts = run.counters.get("affinity_attempts")
    if not attempts:
        return None
    return 100.0 * run.counters["affinity_attempts_refused"] / attempts
