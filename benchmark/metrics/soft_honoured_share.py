"""Of the last job's scheduling decisions that a preferred node affinity term
or a `PreferNoSchedule` taint could sway (a label scorer had something to
normalise by among the feasible nodes), the share placed on a node whose
label score is the largest among the feasible ones, in percent: the program's
`soft_honoured` over its `soft_attempts`, as `metrics_summary()` published
them after the window (the driver copies them into its counters). How often
the soft terms got their way against the resource scores and full pools: 100
where no preference was ever lost, and nothing to read where the program has
no such counters."""


def read(run):
    attempts = run.counters.get("soft_attempts")
    if not attempts:
        return None
    return 100.0 * run.counters["soft_honoured"] / attempts
