"""Operations and bytes of a launch of the scheduling megakernel WITH the
topology-spread filter, beside benchmark/kernel_counts.py (which no later PR
edits and whose block list is the filter-less kernel's): its least bytes and
passes plus what the filter adds to `fused_select_cycle_commit`'s
pallas_call (ops/scheduler_kernel.py `_spread_in_specs` and the wrapper's
`spread_out`).

Blocks, over kernel_counts.MEGAKERNEL_BLOCKS: in, the nodes' domain plane,
the pods' workload and match-bits planes, the count table and its limits
(G x 8 rows each) and the live domains (8 rows); out, the pods' placed-domain
plane, the carried table and an (8, LANE) stats tile.

Passes an iteration of the K loop, over kernel_counts': on the node side the
Z compare-and-ors of the domain mask (3 a domain), the mask's two joins into
the fit, and the placed domain's select and max (2); on the pod side the two
planes the selection sweep brings back and the placed-domain write of the
commit sweep. The table's own arithmetic is G tiles of 8 rows a step and is
not counted (under a thousandth of the node side).
"""

from __future__ import annotations

from benchmark import kernel_counts
from benchmark.kernel_counts import LANE, SUB, _pad

ZONE_TILE = 8  # rows one workload's per-domain counts take


def _extra_rows(n_nodes: int, n_pods: int, workloads: int) -> int:
    table = workloads * ZONE_TILE
    rows_in = _pad(n_nodes, SUB) + 2 * _pad(n_pods, SUB) + 2 * table + ZONE_TILE
    rows_out = _pad(n_pods, SUB) + table + ZONE_TILE
    return rows_in + rows_out


def megakernel_hbm_bytes(n_clusters, n_nodes: int, n_pods: int, k_pods: int, workloads: int) -> float:
    """HBM bytes of one launch over the whole (padded) cluster batch."""
    base = kernel_counts.megakernel_hbm_bytes(n_clusters, n_nodes, n_pods, k_pods)
    return base + _extra_rows(n_nodes, n_pods, workloads) * 4 * _pad(int(n_clusters), LANE)


def node_passes(domains: int) -> int:
    return kernel_counts.MEGAKERNEL_NODE_PASSES + 3 * domains + 2 + 2


POD_PASSES = kernel_counts.MEGAKERNEL_POD_PASSES + 2 + 1


def megakernel_ops(n_clusters, n_nodes: int, n_pods: int, iterations: float, domains: int) -> float:
    """Vector operations of one launch whose K loop runs `iterations` times
    (the mean decisions a cluster a launch: a lower bound of the loop's
    depth, as kernel_counts.megakernel_ops takes it)."""
    per_lane = POD_PASSES * _pad(n_pods, SUB) + node_passes(domains) * _pad(n_nodes, SUB)
    return float(iterations) * per_lane * _pad(int(n_clusters), LANE)
