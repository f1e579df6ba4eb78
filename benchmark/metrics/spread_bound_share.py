"""Of the last job's assignments of pods that carry a topology-spread
constraint, the share at whose instant the skew had closed at least one live
domain to them, in percent: the program's `spread_decisions_bound` over its
`spread_decisions`, as `metrics_summary()` published them after the window
(the driver copies them into its counters). The mechanism's own reading: 0
where no constraint ever binds, and nothing to read where the program has no
such counters."""


def read(run):
    counted = run.counters.get("spread_decisions")
    if not counted:
        return None
    return 100.0 * run.counters["spread_decisions_bound"] / counted
