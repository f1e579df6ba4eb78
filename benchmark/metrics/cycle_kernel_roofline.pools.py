"""The scheduling megakernel's share of its roofline in a cell whose cycle
runs the NodeAffinity and TaintToleration filters and ranks by the exact key:
benchmark/metrics/cycle_kernel_roofline.py with the bytes and passes of
benchmark/pools_kernel_counts.py (the node bit plane, the pods' mask planes,
the counter tile, and the exact key's integer operations a node a step: 151
passes over the node tile a step where kernel_counts.py's float32 core has 20). Says which leg
binds on the `roofline` line. Nothing to read where the driver did not report
the build's term planes (a cell without the filters, or a program without
them)."""

from benchmark import kernel_counts, peaks, pools_kernel_counts
from benchmark.harness import say


def read(run):
    trace, c = run.trace, run.counters
    launches = trace.kernel_events.get("cycle", 0) if trace is not None else 0
    if not launches or c.get("cycle_formulation") != "megakernel" or "affinity_terms" not in c:
        return None
    if c.get("ranking") != "exact":
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips
    terms = int(c["affinity_terms"])
    hbm = pools_kernel_counts.megakernel_hbm_bytes(
        clusters, c["nodes"], c["pods"], c["max_pods_per_cycle"], terms
    )
    launches_per_chip = launches / run.cell.chips
    iterations = c["decisions"] / c["clusters"] / max(c["jobs"], 1) / (launches_per_chip / max(c["jobs"], 1))
    ops = pools_kernel_counts.megakernel_ops(clusters, c["nodes"], c["pods"], iterations, terms)
    least = kernel_counts.roofline(hbm, ops, peak)
    share = 100.0 * least["least_s"] * launches_per_chip / trace.kernel_s["cycle"]
    say(line="roofline", kernel="cycle.pools", bound=least["bound"], launches=launches,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"],
        node_passes=pools_kernel_counts.node_passes(terms))
    return share
