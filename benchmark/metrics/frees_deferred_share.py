"""Of the frees that left a node in the last job (a finish, a failed attempt,
the removal of a running pod), the share the pending-free channel carried past
the next scheduling cycle, in percent: the program's `frees_deferred` over its
`frees_total`, as `metrics_summary()` published them after the window. About
100 x chain / interval where finishes spread evenly over a cycle (2.9 at the
reference's delays and a 10 s cycle); 0 where the delays are zero."""

from benchmark.free_kernel_counts import program_counters


def read(run):
    counted = program_counters("frees_total", "frees_deferred")
    if counted is None or not counted["frees_total"]:
        return None
    return 100.0 * counted["frees_deferred"] / counted["frees_total"]
