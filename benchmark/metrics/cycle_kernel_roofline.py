"""The scheduling megakernel's share of its roofline: the least time the chip
could take for the launches in the trace, over the time they took. Says which
bound on the `roofline` line; only where the cycle runs as the megakernel."""

from benchmark import kernel_counts, peaks
from benchmark.harness import say


def read(run):
    trace, c = run.trace, run.counters
    launches = trace.kernel_events.get("cycle", 0) if trace is not None else 0
    if not launches or c.get("cycle_formulation") != "megakernel":
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips  # one chip's shard, as the trace is averaged
    hbm = kernel_counts.megakernel_hbm_bytes(clusters, c["nodes"], c["pods"], c["max_pods_per_cycle"])
    launches_per_chip = launches / run.cell.chips
    iterations = c["decisions"] / c["clusters"] / max(c["jobs"], 1) / (launches_per_chip / max(c["jobs"], 1))
    ops = kernel_counts.megakernel_ops(clusters, c["nodes"], c["pods"], iterations)
    least = kernel_counts.roofline(hbm, ops, peak)
    share = 100.0 * least["least_s"] * launches_per_chip / trace.kernel_s["cycle"]
    say(line="roofline", kernel="cycle", bound=least["bound"], launches=launches,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"])
    return share
