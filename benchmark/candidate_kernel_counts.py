"""Operations and bytes one launch of the candidate kernel needs
(ops/scheduler_kernel.py `fused_schedule_cycle`), computed from its shapes, as
benchmark/kernel_counts.py does for the megakernel: kept with the benchmark so
that no PR that claims a gain can change how the roofline share is counted.

Bytes: one HBM read of each input block and one write of each output block a
launch. Inputs 3 node-shaped (alive, allocatable cpu, ram) + 3 K-shaped
(valid, request cpu, ram); outputs 2 node-shaped + 3 K-shaped (assign, any
fit, best). A grid program holds 128 clusters on its lanes, so one cluster
pays for 128: the share says how much of the tile is padding.

Operations: elementwise int32/float32 vector passes over the node tile per
iteration of the K loop, read off `_fit_score_place`. With float32 ranking
about 20 (kernel_counts.MEGAKERNEL_NODE_PASSES: the same function). With the
exact key (pipeline.exact_least_allocated_key): fit 4; three digits of long
division for each of two quotients, 17 passes a digit (shift, two converts,
divide, floor, multiply, subtract, two compares, two converts, two adds, two
adjusts, two selects) = 102; packing the key 10; the two-level least and the
highest slot 9; placing the pod 6; any-fit 2.
"""

from __future__ import annotations

from benchmark.kernel_counts import LANE, MEGAKERNEL_NODE_PASSES, SUB, _pad

KERNEL = "fused_schedule_cycle"  # the pallas_call's name= in ops/scheduler_kernel.py: its device events start with it

NODE_BLOCKS = {"in": 3, "out": 2}
CAND_BLOCKS = {"in": 3, "out": 3}
NODE_PASSES = {"float32": MEGAKERNEL_NODE_PASSES, "exact": 4 + 102 + 10 + 9 + 6 + 2}


def candidate_hbm_bytes(n_clusters: float, n_nodes: int, k_pods: int) -> int:
    rows = sum(NODE_BLOCKS.values()) * _pad(n_nodes, SUB) + sum(CAND_BLOCKS.values()) * _pad(k_pods, SUB)
    return rows * 4 * _pad(int(n_clusters), LANE)


def candidate_ops(n_clusters: float, n_nodes: int, iterations: float, ranking: str) -> float:
    """Vector operations of one launch whose K loop runs `iterations` times
    (callers pass the mean decisions a launch, a lower bound of the depth)."""
    return float(iterations) * NODE_PASSES[ranking] * _pad(n_nodes, SUB) * _pad(int(n_clusters), LANE)


def kernel_seconds(trace):
    """Device seconds of the kernel's events in a reduced trace (a custom call
    has no children, so its self time is its duration), or None where the
    trace holds none."""
    seconds = [s for name, s in trace.op_self_s.items() if name.startswith(KERNEL)]
    return sum(seconds) if seconds else None
