"""Operations and bytes one launch of the event kernel needs
(ops/scheduler_kernel.py `fused_event_scatter`), computed from its shapes, as
benchmark/free_kernel_counts.py does for the free kernel: kept with the
benchmark so that no PR that claims a gain can change how the roofline share
is counted.

Bytes: one HBM read of each input block and one write of each output block a
launch. Inputs 5 event-shaped (kind, slot, time, sequence, valid: the chunk)
and the accumulators, which the launch updates in place: 2 node-shaped
(created, removal time; 3 under node faults, the crash's removal time) and 3
pod-shaped (create time, create sequence, removal time), each read whole and
written whole. The SMEM list of live tiles is not HBM traffic.

Operations: elementwise int32/float32 vector passes per step of the kernel's
loop, read off `_event_kernel`. A step applies one event of every lane: over
the node block the slot one-hot (1), the creation's mask and select (2), the
removal's mask, minimum and select (3), and under node faults the recovery's
and the crash's masks with the crash plane's minimum and select (5); over
each row of a live pod tile the one-hot (2) and three accumulators' mask,
combine and select (9). The loop runs to the deepest lane of a tile and
sweeps the tiles between the lowest and the highest pod slot the chunk
names; callers pass the mean events a cluster a launch, a lower bound of
that depth, and the count takes one live tile, the fewest a step with a pod
event sweeps, so it is never above what ran.

Launches: one a pass of the event chunk loop, and a pass in every window in
which some cluster's event application is due. The program counts, a
cluster, those windows (`event_windows`) and publishes the largest count of
the batch: never more than the launches (a window whose due events overflow
the chunk takes a second), as benchmark/metrics/free_kernel_roofline.py
counts its own.
"""

from __future__ import annotations

from benchmark.kernel_counts import LANE, SUB, _pad

KERNEL = "fused_event_scatter"  # the pallas_call's name=: its device events start with it

EVENT_BLOCKS = 5
NODE_PLANES = {False: 2, True: 3}  # by node faults; each read and written
POD_PLANES = 3
NODE_PASSES = {False: 6, True: 11}
POD_PASSES = 11
LIVE_TILE_ROWS = 128  # ops/scheduler_kernel.py _row_tiles: the rows a step sweeps at least


def event_hbm_bytes(n_clusters: float, n_nodes: int, n_pods: int, chunk: int, node_faults: bool) -> int:
    rows = (
        EVENT_BLOCKS * _pad(chunk, SUB)
        + 2 * NODE_PLANES[node_faults] * _pad(n_nodes, SUB)
        + 2 * POD_PLANES * _pad(n_pods, SUB)
    )
    return rows * 4 * _pad(int(n_clusters), LANE)


def event_ops(n_clusters: float, n_nodes: int, n_pods: int, steps: float, node_faults: bool) -> float:
    """Vector operations of one launch whose loop runs `steps` times."""
    per_lane = POD_PASSES * min(LIVE_TILE_ROWS, _pad(n_pods, SUB)) + NODE_PASSES[node_faults] * _pad(n_nodes, SUB)
    return float(steps) * per_lane * _pad(int(n_clusters), LANE)


def kernel_seconds(trace):
    """Device seconds of the kernel's events in a reduced trace (a custom call
    has no children, so its self time is its duration), or None where the
    trace holds none."""
    seconds = [s for name, s in trace.op_self_s.items() if name.startswith(KERNEL)]
    return sum(seconds) if seconds else None
