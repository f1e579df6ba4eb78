"""Operations and bytes of a launch of the scheduling megakernel WITH the
label filters and kube-scheduler's integer scorers, beside
benchmark/pools_kernel_counts.py (which no later PR edits and whose node
passes are the exact key's): its least bytes plus what the integer scorers add
to `fused_select_cycle_commit`'s pallas_call (ops/scheduler_kernel.py
`_kube_operands`), and an ops leg of its own. The function counts the work the
semantics need (docs/PARITY.md "Scoring as kube-scheduler scores"), whatever
implements it.

Blocks, over pools_kernel_counts' (the node bit plane, the pods' T term planes
and untolerated-taint plane in, an (8, LANE) counter tile out): in, the two
capacity planes and the pods' S preferred-term planes, packed weights and
untolerated soft taints; out, one more (8, LANE) int32 counter tile.

Passes over the node tile an iteration of the K loop, read off
batched/pipeline.py (`integer_scores`, `soft_raw_scores`, `floor_quotient`,
`integer_best_node`, `soft_honoured`) and `_fit_score_place`:

- Fit, alive and the label filters: pools_kernel_counts';
- the frees in units: a subtract, a shift and a multiply a resource (6);
- a one-digit quotient: a conversion, a multiply, a conversion back, a
  multiply, a subtract, a shift and an add (7), with its numerator's multiply
  by 100 and its capacity guard's compare and select (3): 10;
- NodeResourcesFit: two quotients, an add and a shift (22);
- NodeResourcesBalancedAllocation: two multiplies, a subtract, a negate and a
  max; a multiply and a subtract for the numerator; the quotient without its
  own multiply by 100 (9); an add into the total (17);
- NodeAffinity's score: a term an and, a compare, a select and an add (4 S,
  less one add); its M, a select and a max (2); the quotient (10); its weight
  and the add (2): 4 S + 13;
- TaintToleration's score: an and; a soft taint bit a shift, an and and an add
  (3 B, less one add); its M (2); the quotient (10); the reversal, the weight
  and the add (3): 3 B + 15;
- the label scorers' part kept for the counter: an add (1); the total's mask,
  a select (1); the best node: a max, a compare, an and, a select, a max (5);
- `soft_honoured`: two selects and two maxima (4);
- any node fits and the placement: pools_kernel_counts'.

On the pod side, over pools_kernel_counts': the planes the selection sweep
brings back with the chosen row (S + 2).
"""

from __future__ import annotations

from benchmark import kernel_counts, pools_kernel_counts
from benchmark.kernel_counts import LANE, SUB, _pad

QUOTIENT_PASSES = 10
UNITS_PASSES = 6
FIT_SCORE_PASSES = 2 * QUOTIENT_PASSES + 2
BALANCED_PASSES = 5 + 2 + (QUOTIENT_PASSES - 1) + 1
BEST_NODE_PASSES = 1 + 1 + 5
HONOURED_PASSES = 4


def affinity_score_passes(soft_terms: int) -> int:
    return (4 * soft_terms - 1) + 2 + QUOTIENT_PASSES + 2


def taint_score_passes(soft_taints: int) -> int:
    return 1 + max(3 * soft_taints - 1, 0) + 2 + QUOTIENT_PASSES + 3


def node_passes(terms: int, soft_terms: int, soft_taints: int) -> int:
    return (
        pools_kernel_counts.FIT_PASSES + pools_kernel_counts.label_filter_passes(terms)
        + UNITS_PASSES + FIT_SCORE_PASSES + BALANCED_PASSES
        + affinity_score_passes(soft_terms) + taint_score_passes(soft_taints)
        + BEST_NODE_PASSES + HONOURED_PASSES
        + pools_kernel_counts.ANY_FIT_PASSES + pools_kernel_counts.PLACE_PASSES
    )


def pod_passes(terms: int, soft_terms: int) -> int:
    return pools_kernel_counts.pod_passes(terms) + soft_terms + 2


def _extra_rows(n_nodes: int, n_pods: int, soft_terms: int) -> int:
    rows_in = 2 * _pad(n_nodes, SUB) + (soft_terms + 2) * _pad(n_pods, SUB)
    rows_out = SUB
    return rows_in + rows_out


def megakernel_hbm_bytes(n_clusters, n_nodes: int, n_pods: int, k_pods: int, terms: int, soft_terms: int) -> float:
    """HBM bytes of one launch over the whole (padded) cluster batch."""
    base = pools_kernel_counts.megakernel_hbm_bytes(n_clusters, n_nodes, n_pods, k_pods, terms)
    return base + _extra_rows(n_nodes, n_pods, soft_terms) * 4 * _pad(int(n_clusters), LANE)


def megakernel_ops(
    n_clusters, n_nodes: int, n_pods: int, iterations: float, terms: int, soft_terms: int, soft_taints: int
) -> float:
    """Vector operations of one launch whose K loop runs `iterations` times
    (the mean attempts a cluster a launch: a lower bound of the loop's depth,
    as kernel_counts.megakernel_ops takes it)."""
    per_lane = pod_passes(terms, soft_terms) * _pad(n_pods, SUB) + node_passes(
        terms, soft_terms, soft_taints
    ) * _pad(n_nodes, SUB)
    return float(iterations) * per_lane * _pad(int(n_clusters), LANE)


assert kernel_counts.LANE == LANE
