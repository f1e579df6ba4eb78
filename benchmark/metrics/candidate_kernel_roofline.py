"""The candidate kernel's share of its roofline: the least time the chip
could take for the launches in the trace, over the time they took. Says which
bound on a `roofline` line. One cluster fills one of a grid program's 128
lanes, so the share is small by construction there and says so."""

from benchmark import candidate_kernel_counts, kernel_counts, peaks
from benchmark.harness import say


def read(run):
    c = run.counters
    launches = c.get("candidate_kernel_launches")
    seconds = candidate_kernel_counts.kernel_seconds(run.trace) if run.trace is not None else None
    if not launches or seconds is None or c.get("cycle_formulation") != "candidate":
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips
    hbm = candidate_kernel_counts.candidate_hbm_bytes(clusters, c["nodes"], c["max_pods_per_cycle"])
    iterations = c["decisions"] / c["clusters"] / launches
    ops = candidate_kernel_counts.candidate_ops(clusters, c["nodes"], iterations, c["node_ranking"])
    least = kernel_counts.roofline(hbm, ops, peak)
    say(line="roofline", kernel="candidate", bound=least["bound"], launches=launches,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"],
        real_lanes_of_128=min(clusters, 128) / 128)
    return 100.0 * least["least_s"] * launches / seconds
