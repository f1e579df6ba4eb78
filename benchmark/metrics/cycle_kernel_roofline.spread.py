"""The scheduling megakernel's share of its roofline in a cell whose cycle
runs the topology-spread filter: benchmark/metrics/cycle_kernel_roofline.py
with the bytes and passes of benchmark/spread_kernel_counts.py (the domain
plane, the pods' two planes in and one out, and the table a launch), which
kernel_counts.py's block list would understate. Says which bound on the
`roofline` line. Nothing to read where the driver did not report the build's
spread shape (a cell without the filter, or a program without it)."""

from benchmark import kernel_counts, peaks, spread_kernel_counts
from benchmark.harness import say


def read(run):
    trace, c = run.trace, run.counters
    launches = trace.kernel_events.get("cycle", 0) if trace is not None else 0
    if not launches or c.get("cycle_formulation") != "megakernel" or "spread_workloads" not in c:
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips
    hbm = spread_kernel_counts.megakernel_hbm_bytes(
        clusters, c["nodes"], c["pods"], c["max_pods_per_cycle"], c["spread_workloads"]
    )
    launches_per_chip = launches / run.cell.chips
    iterations = c["decisions"] / c["clusters"] / max(c["jobs"], 1) / (launches_per_chip / max(c["jobs"], 1))
    ops = spread_kernel_counts.megakernel_ops(clusters, c["nodes"], c["pods"], iterations, c["spread_domains"])
    least = kernel_counts.roofline(hbm, ops, peak)
    share = 100.0 * least["least_s"] * launches_per_chip / trace.kernel_s["cycle"]
    say(line="roofline", kernel="cycle.spread", bound=least["bound"], launches=launches,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"])
    return share
