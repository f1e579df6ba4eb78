"""The scheduling megakernel's share of its roofline in a cell whose cycle
ranks as kube-scheduler ranks (the label filters and the four integer
scorers): benchmark/metrics/cycle_kernel_roofline.pools.py with the bytes and
passes of benchmark/kubescore_kernel_counts.py (the two capacity planes, the
pods' soft planes, one more counter tile; the integer chain's passes over the
node tile a step). Says which leg binds on the `roofline` line. Nothing to
read where the driver did not report the build's soft planes (a cell that
ranks otherwise, or a program without the scorers)."""

from benchmark import kernel_counts, kubescore_kernel_counts, peaks
from benchmark.harness import say


def read(run):
    trace, c = run.trace, run.counters
    launches = trace.kernel_events.get("cycle", 0) if trace is not None else 0
    if not launches or c.get("cycle_formulation") != "megakernel" or "soft_terms" not in c:
        return None
    if c.get("ranking") != "integer" or "affinity_terms" not in c:
        return None
    peak = peaks.for_device(run.device["kind"])
    clusters = c["clusters"] / run.cell.chips
    terms, soft_terms, soft_taints = int(c["affinity_terms"]), int(c["soft_terms"]), int(c["soft_taints"])
    hbm = kubescore_kernel_counts.megakernel_hbm_bytes(
        clusters, c["nodes"], c["pods"], c["max_pods_per_cycle"], terms, soft_terms
    )
    launches_per_chip = launches / run.cell.chips
    iterations = c["decisions"] / c["clusters"] / max(c["jobs"], 1) / (launches_per_chip / max(c["jobs"], 1))
    ops = kubescore_kernel_counts.megakernel_ops(
        clusters, c["nodes"], c["pods"], iterations, terms, soft_terms, soft_taints
    )
    least = kernel_counts.roofline(hbm, ops, peak)
    share = 100.0 * least["least_s"] * launches_per_chip / trace.kernel_s["cycle"]
    say(line="roofline", kernel="cycle.kubescore", bound=least["bound"], launches=launches,
        hbm_bytes_per_launch=hbm, ops_per_launch=ops, least_s_per_launch=least["least_s"],
        node_passes=kubescore_kernel_counts.node_passes(terms, soft_terms, soft_taints))
    return share
