"""Operations and bytes a kernel launch needs, computed from its shapes.

Kept with the benchmark so that no PR that claims a gain can change how a
roofline share is counted. Bytes: one HBM read of each input block and one
write of each output block per launch (the block list of the kernel's
pallas_call; the VMEM scratch is not HBM traffic). Operations: elementwise
int32/float32 vector operations per loop iteration, read off the kernel body.
"""

from __future__ import annotations

from typing import Dict

LANE = 128  # clusters per grid program
SUB = 8  # int32/float32 sublane tile


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


# The scheduling megakernel (ops/scheduler_kernel.py fused_select_cycle_commit):
# inputs 3 node-shaped + 9 pod-shaped + 3 K-shaped blocks, outputs 2 node-shaped
# + 4 pod-shaped + the (8, LANE) stats block; one pod-shaped VMEM scratch.
MEGAKERNEL_BLOCKS = {
    "in": {"node": 3, "pod": 9, "cand": 3, "stat": 0},
    "out": {"node": 2, "pod": 4, "cand": 0, "stat": 1},
    "scratch": {"node": 0, "pod": 1, "cand": 0, "stat": 0},
}
# Elementwise passes per iteration of the K loop, per lane: the lexicographic
# argmin (16 over pods), the request gather (4), fit + score + place (20 over
# nodes), the commit scatter by one-hot mask (14), queue-time fold and the
# remaining-mask update (3).
MEGAKERNEL_POD_PASSES = 37
MEGAKERNEL_NODE_PASSES = 20


def _rows(blocks: Dict[str, int], n_nodes: int, n_pods: int, k_pods: int) -> int:
    return (
        blocks["node"] * _pad(n_nodes, SUB)
        + blocks["pod"] * _pad(n_pods, SUB)
        + blocks["cand"] * _pad(k_pods, SUB)
        + blocks["stat"] * SUB
    )


def megakernel_vmem_block_bytes(n_nodes: int, n_pods: int, k_pods: int) -> int:
    """Resident VMEM of one grid program, double-buffered: the quantity the
    engine's `select_commit_kernel_fits` gate holds under 80% of the scoped
    limit (the test checks the two agree at the cell shapes)."""
    rows = sum(_rows(MEGAKERNEL_BLOCKS[k], n_nodes, n_pods, k_pods) for k in MEGAKERNEL_BLOCKS)
    return 2 * rows * 4 * LANE


def megakernel_hbm_bytes(n_clusters: int, n_nodes: int, n_pods: int, k_pods: int) -> int:
    """HBM bytes of one launch over the whole (padded) cluster batch."""
    rows = _rows(MEGAKERNEL_BLOCKS["in"], n_nodes, n_pods, k_pods) + _rows(
        MEGAKERNEL_BLOCKS["out"], n_nodes, n_pods, k_pods
    )
    return rows * 4 * _pad(n_clusters, LANE)


def megakernel_ops(n_clusters: int, n_nodes: int, n_pods: int, iterations: float) -> float:
    """Vector operations of one launch whose K loop runs `iterations` times.
    The loop runs to the deepest lane of a tile; callers pass the mean
    decisions per cluster per launch, a lower bound of that depth, so the
    count is never above what ran."""
    per_lane = MEGAKERNEL_POD_PASSES * _pad(n_pods, SUB) + MEGAKERNEL_NODE_PASSES * _pad(n_nodes, SUB)
    return float(iterations) * per_lane * _pad(n_clusters, LANE)


def roofline(bytes_moved: float, operations: float, peaks: Dict) -> Dict:
    """The least time the chip could take for this work, and which bound."""
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    by_compute = operations / peaks["bf16_flops_per_s"]
    return {
        "least_s": max(by_memory, by_compute),
        "bound": "memory" if by_memory >= by_compute else "compute",
    }
