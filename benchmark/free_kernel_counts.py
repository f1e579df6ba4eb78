"""Operations and bytes one launch of the free kernel needs
(ops/scheduler_kernel.py `fused_free_resources`), computed from its shapes, as
benchmark/kernel_counts.py does for the megakernel: kept with the benchmark so
that no PR that claims a gain can change how the roofline share is counted.

Bytes: one HBM read of each input block and one write of each output block a
launch. Inputs 6 pod-shaped (freed, node, request cpu, ram, finish bit, the
estimator's sample) + 2 node-shaped (allocatable cpu, ram); outputs 2
node-shaped + the (8, LANE) block of the duration estimator's sums. The SMEM
list of live tiles is not HBM traffic.

Operations: elementwise int32/float32 vector passes per step of the kernel's
loop, read off `_free_kernel` and `_select_first`. A step takes one freed row
of every lane: over each row of a live pod tile the remaining mask (3), the
first-row test (2), the running slot (1), its row numbers (1) and the five
values brought along (5); over the node block the one-hot (1) and the two
masked adds (4). The loop runs to the deepest lane of a tile and sweeps the
tiles that hold a freed row; callers pass the mean frees a cluster a launch,
a lower bound of that depth, and the count takes one live tile, the fewest a
step can sweep, so it is never above what ran.

Launches: one in every window in which some cluster's event application is
due (the window razor skips the others). The program counts, a cluster, the
windows in which its own was due (`event_windows`,
kubernetriks_tpu/batched/state.py MetricArrays), and publishes the largest
count of the batch: never more than the launches, and equal to them where
every cluster carries a like load, as in the montecarlo mixes. Every job of
a cell repeats the same windows.
"""

from __future__ import annotations

from benchmark.kernel_counts import LANE, SUB, _pad

KERNEL = "fused_free_resources"  # the pallas_call's name=: its device events start with it

POD_BLOCKS = {"in": 6, "out": 0}
NODE_BLOCKS = {"in": 2, "out": 2}
STAT_ROWS = 8
POD_PASSES = 12
NODE_PASSES = 5
LIVE_TILE_ROWS = 128  # ops/scheduler_kernel.py _row_tiles: the rows a step sweeps at least


def free_hbm_bytes(n_clusters: float, n_nodes: int, n_pods: int) -> int:
    rows = (
        sum(POD_BLOCKS.values()) * _pad(n_pods, SUB)
        + sum(NODE_BLOCKS.values()) * _pad(n_nodes, SUB)
        + STAT_ROWS
    )
    return rows * 4 * _pad(int(n_clusters), LANE)


def free_ops(n_clusters: float, n_nodes: int, n_pods: int, steps: float) -> float:
    """Vector operations of one launch whose loop runs `steps` times."""
    per_lane = POD_PASSES * min(LIVE_TILE_ROWS, _pad(n_pods, SUB)) + NODE_PASSES * _pad(n_nodes, SUB)
    return float(steps) * per_lane * _pad(int(n_clusters), LANE)


def kernel_seconds(trace):
    """Device seconds of the kernel's events in a reduced trace (a custom call
    has no children, so its self time is its duration), or None where the
    trace holds none."""
    seconds = [s for name, s in trace.op_self_s.items() if name.startswith(KERNEL)]
    return sum(seconds) if seconds else None


def program_counters(*names):
    """The named counters of the program's recorder as the last
    `metrics_summary()` left them, or None where the program has no recorder
    or never set one of them (a commit before PR 32)."""
    from benchmark import program_spans

    program = program_spans._program()
    if program is None:
        return None
    counters = program[0].counters
    if any(name not in counters for name in names):
        return None
    return {name: counters[name] for name in names}
