"""Operations and bytes of a launch of the scheduling megakernel WITH the
NodeAffinity and TaintToleration filters and the exact ranking key, beside
benchmark/kernel_counts.py (which no later PR edits and whose block list and
passes are the float32, filter-less kernel's): its least bytes plus what the
two filters add to `fused_select_cycle_commit`'s pallas_call
(ops/scheduler_kernel.py `_affinity_operands`), and an ops leg of its own. The
function counts the work the semantics need, whatever implements it.

Blocks, over kernel_counts.MEGAKERNEL_BLOCKS: in, the nodes' bit plane and the
pods' T term planes and untolerated-taint plane; out, an (8, LANE) int32
counter tile.

Passes over the node tile an iteration of the K loop, read off
batched/pipeline.py (`exact_least_allocated_key`, `exact_best_node`,
`affinity_node_masks`) and `_fit_score_place`:

- Fit and alive: two compares and two ands (4);
- the label filters: a term an and and a compare, the terms ored (3 T - 1);
  the taints an and and a compare (2); their two joins into the mask (2); the
  reduction over the mask without them that `affinity_attempts_refused`
  needs, a cast and a max (2);
- the exact key: two long divisions of three digits, a digit a shift, a
  conversion, a float division, a floor, a conversion back, a multiply and a
  subtract for the remainder, two compares, two casts and two adds that
  correct the digit, two selects and two adds that correct the remainder
  (17, so 102), a guarded float divisor each (3, so 6), and the two words'
  assembly: an add; two shifts and three adds; the valid mask's two compares
  and two ands; two selects and a mask (14): 122;
- the best node: two minima, two compares, two ands, two selects, a max (9);
- any node fits: a cast and a max (2); the placement: a compare, an and, two
  selects and two subtracts (6).

On the pod side, over kernel_counts': the planes the selection sweep brings
back with the chosen row (T + 1).
"""

from __future__ import annotations

from benchmark import kernel_counts
from benchmark.kernel_counts import LANE, SUB, _pad

EXACT_KEY_PASSES = 2 * 3 * 17 + 2 * 3 + 14
FIT_PASSES, BEST_NODE_PASSES, ANY_FIT_PASSES, PLACE_PASSES = 4, 9, 2, 6


def label_filter_passes(terms: int) -> int:
    return (3 * terms - 1) + 2 + 2 + 2


def node_passes(terms: int) -> int:
    return (
        FIT_PASSES + label_filter_passes(terms) + EXACT_KEY_PASSES + BEST_NODE_PASSES
        + ANY_FIT_PASSES + PLACE_PASSES
    )


def pod_passes(terms: int) -> int:
    return kernel_counts.MEGAKERNEL_POD_PASSES + terms + 1


def _extra_rows(n_nodes: int, n_pods: int, terms: int) -> int:
    rows_in = _pad(n_nodes, SUB) + (terms + 1) * _pad(n_pods, SUB)
    rows_out = SUB
    return rows_in + rows_out


def megakernel_hbm_bytes(n_clusters, n_nodes: int, n_pods: int, k_pods: int, terms: int) -> float:
    """HBM bytes of one launch over the whole (padded) cluster batch."""
    base = kernel_counts.megakernel_hbm_bytes(n_clusters, n_nodes, n_pods, k_pods)
    return base + _extra_rows(n_nodes, n_pods, terms) * 4 * _pad(int(n_clusters), LANE)


def megakernel_ops(n_clusters, n_nodes: int, n_pods: int, iterations: float, terms: int) -> float:
    """Vector operations of one launch whose K loop runs `iterations` times
    (the mean attempts a cluster a launch: a lower bound of the loop's depth,
    as kernel_counts.megakernel_ops takes it)."""
    per_lane = pod_passes(terms) * _pad(n_pods, SUB) + node_passes(terms) * _pad(n_nodes, SUB)
    return float(iterations) * per_lane * _pad(int(n_clusters), LANE)
