"""Scheduler plugin registry: the reference's Fit and LeastAllocatedResources
(reference: src/core/scheduler/plugin.rs), the packing-side scorers
(MostAllocatedResources, BalancedResourceAllocation), kube-scheduler's
filters PodTopologySpread (DoNotSchedule), NodeAffinity (the required half)
and TaintToleration (NoSchedule), and its default score plugins in integers
(NodeResourcesFit, NodeResourcesBalancedAllocation, and the score halves of
NodeAffinity and TaintToleration: docs/PARITY.md "Scoring as kube-scheduler
scores"). The batched device pipeline lowers every one of them.

A filter sees the pod, the nodes still in the running and the scheduler's
cache (`SchedulerCache`: every cached node, the cached pods, and which pods
the scheduler has assigned to which node); a scorer sees one pod and one node,
and may then normalise its scores over the nodes that passed the filters.

The plugin NAME constants below are the shared vocabulary between this
scalar registry and the device-plugin registry in
kubernetriks_tpu/batched/pipeline.py: a profile referencing these names runs
on both paths with one definition of the semantics (the batched registry
validates against them at engine construction and raises loudly on a name it
cannot lower)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple, Union

from kubernetriks_tpu.core.types import Node, Pod, Toleration, TopologySpreadConstraint

# Shared plugin-name constants (scalar registry keys == device registry keys).
FIT = "Fit"
LEAST_ALLOCATED = "LeastAllocatedResources"
MOST_ALLOCATED = "MostAllocatedResources"
BALANCED = "BalancedResourceAllocation"
TOPOLOGY_SPREAD = "PodTopologySpread"
NODE_AFFINITY = "NodeAffinity"
TAINT_TOLERATION = "TaintToleration"
NODE_RESOURCES_FIT = "NodeResourcesFit"
BALANCED_ALLOCATION = "NodeResourcesBalancedAllocation"
# kube-scheduler's own score plugins: integers 0-100, added by integer
# weights. NodeAffinity and TaintToleration are one plugin each upstream and
# here: the filter half and the score half under one name.
INTEGER_SCORE_PLUGINS = (NODE_RESOURCES_FIT, BALANCED_ALLOCATION, NODE_AFFINITY, TAINT_TOLERATION)
MAX_NODE_SCORE = 100


@dataclass
class SchedulerCache:
    """What a filter may read besides its arguments: the scheduler's cache
    as it stands when the pod is filtered (core/scheduler/scheduler.py:
    `objects_cache` and `assignments`). `nodes` is EVERY cached node, whatever
    an earlier filter of the chain dropped; a pod appears under its node from
    the instant the scheduler assigns it (so the next pod of the same cycle
    sees it) until the scheduler learns that it left."""

    nodes: Dict[str, Node] = field(default_factory=dict)
    pods: Dict[str, Pod] = field(default_factory=dict)
    assignments: Dict[str, Set[str]] = field(default_factory=dict)


class FilterPlugin:
    def filter(self, pod: Pod, nodes: List[Node], cache: SchedulerCache) -> List[Node]:
        raise NotImplementedError


class ScorePlugin:
    def score(self, pod: Pod, node: Node) -> float:
        raise NotImplementedError

    def normalize(self, scores: List) -> List:
        """The scores of the nodes that passed the filters, in their order,
        as they enter the weighted sum (upstream's NormalizeScore; most
        plugins leave them as they are)."""
        return scores


class Fit(FilterPlugin):
    """Keep nodes whose allocatable covers the pod's requests
    (reference: src/core/scheduler/plugin.rs:33-45)."""

    def filter(self, pod: Pod, nodes: List[Node], cache: SchedulerCache) -> List[Node]:
        requests = pod.spec.resources.requests
        return [
            node
            for node in nodes
            if requests.cpu <= node.status.allocatable.cpu
            and requests.ram <= node.status.allocatable.ram
        ]


class LeastAllocatedResources(ScorePlugin):
    """Mean of the percentage of cpu+ram left after placement, relative to the
    node's current allocatable (reference: src/core/scheduler/plugin.rs:47-63)."""

    def score(self, pod: Pod, node: Node) -> float:
        requests = pod.spec.resources.requests
        allocatable = node.status.allocatable
        # Zero allocatable yields NaN, matching the reference's f64 division
        # (plugin.rs:54-62); NaN never displaces a finite score in the `>=`
        # argmax (the degenerate NaN-seed case is documented in DESIGN §9.4).
        cpu_score = (
            (allocatable.cpu - requests.cpu) * 100.0 / allocatable.cpu
            if allocatable.cpu
            else float("nan")
        )
        ram_score = (
            (allocatable.ram - requests.ram) * 100.0 / allocatable.ram
            if allocatable.ram
            else float("nan")
        )
        return (cpu_score + ram_score) / 2.0


class MostAllocatedResources(ScorePlugin):
    """Best-fit packing: the exact negation of LeastAllocatedResources per
    resource — mean percentage of the node's current allocatable the pod
    would CONSUME, so the tightest-fitting node scores highest. Zero
    allocatable keeps the NaN convention above (the device pipeline lowers
    it to -inf; neither ever wins the argmax)."""

    def score(self, pod: Pod, node: Node) -> float:
        requests = pod.spec.resources.requests
        allocatable = node.status.allocatable
        cpu_score = (
            (requests.cpu - allocatable.cpu) * 100.0 / allocatable.cpu
            if allocatable.cpu
            else float("nan")
        )
        ram_score = (
            (requests.ram - allocatable.ram) * 100.0 / allocatable.ram
            if allocatable.ram
            else float("nan")
        )
        return (cpu_score + ram_score) / 2.0


class BalancedResourceAllocation(ScorePlugin):
    """100 minus the percentage-point imbalance between the cpu and ram
    fractions of the node's current allocatable the pod would consume —
    favors placements that drain both resources evenly (the shape of
    upstream Kubernetes' NodeResourcesBalancedAllocation, stated against
    allocatable like the two scorers above)."""

    def score(self, pod: Pod, node: Node) -> float:
        requests = pod.spec.resources.requests
        allocatable = node.status.allocatable
        if not allocatable.cpu or not allocatable.ram:
            return float("nan")
        cpu_frac = requests.cpu / allocatable.cpu
        ram_frac = requests.ram / allocatable.ram
        return 100.0 - abs(cpu_frac - ram_frac) * 100.0


class NodeResourcesFit(ScorePlugin):
    """kube-scheduler's NodeResourcesFit score, strategy LeastAllocated over
    cpu and memory at weight 1 (docs/PARITY.md "Scoring as kube-scheduler
    scores"): per resource floor((A - U) * 100 / A) with A the node's
    capacity and U what would be requested on it with the pod, 0 where A is
    0; the floor of their mean. Python integers throughout."""

    def score(self, pod: Pod, node: Node) -> int:
        requests, free, capacity = pod.spec.resources.requests, node.status.allocatable, node.status.capacity

        def left(a: int, f: int, q: int) -> int:
            return (f - q) * MAX_NODE_SCORE // a if a else 0

        return (
            left(capacity.cpu, free.cpu, requests.cpu) + left(capacity.ram, free.ram, requests.ram)
        ) // 2


class NodeResourcesBalancedAllocation(ScorePlugin):
    """kube-scheduler's NodeResourcesBalancedAllocation over cpu and memory:
    floor(100 - 50 |U_cpu / A_cpu - U_ram / A_ram|) as an exact rational
    (upstream rounds through float64), 0 where a capacity is 0."""

    def score(self, pod: Pod, node: Node) -> int:
        requests, free, capacity = pod.spec.resources.requests, node.status.allocatable, node.status.capacity
        a_cpu, a_ram = capacity.cpu, capacity.ram
        if not a_cpu or not a_ram:
            return 0
        u_cpu = a_cpu - free.cpu + requests.cpu
        u_ram = a_ram - free.ram + requests.ram
        whole = a_cpu * a_ram
        return (MAX_NODE_SCORE * whole - 50 * abs(u_cpu * a_ram - u_ram * a_cpu)) // whole


def normalize_by_max(scores: List[int], reverse: bool) -> List[int]:
    """Upstream's DefaultNormalizeScore(100, reverse, scores): every raw score
    as its share of the largest one, in whole points; no largest score leaves
    0 everywhere (100 reversed)."""
    most = max(scores, default=0)
    if most == 0:
        return [MAX_NODE_SCORE if reverse else 0] * len(scores)
    shares = [MAX_NODE_SCORE * s // most for s in scores]
    return [MAX_NODE_SCORE - s for s in shares] if reverse else shares


class UnsupportedSpreadConstraint(ValueError):
    """A pod carries a topology-spread constraint (or a part of one) that
    this scheduler does not implement. Raised where the constraint is used,
    naming the part: never ignored, since a constraint that is dropped
    silently puts pods on other nodes."""


def supported_spread_constraint(pod: Pod) -> Optional[TopologySpreadConstraint]:
    """The one constraint of `pod` that PodTopologySpread implements, None
    for a pod without constraints; raises UnsupportedSpreadConstraint naming
    what is refused. The scalar filter and the batched trace compiler both
    call this, so the two paths refuse the same things."""
    constraints = pod.spec.topology_spread_constraints
    if not constraints:
        return None
    name = pod.metadata.name

    def refuse(what: str):
        return UnsupportedSpreadConstraint(
            f"pod {name!r}: {what} is not supported: PodTopologySpread implements one "
            "constraint a pod with whenUnsatisfiable DoNotSchedule and a matchLabels selector"
        )

    if pod.spec.names_nodes():
        raise refuse(
            "a topology-spread constraint together with a nodeSelector, a node affinity or a "
            "toleration (upstream's nodeAffinityPolicy / nodeTaintsPolicy decide which nodes count "
            "for the skew)"
        )
    if len(constraints) > 1:
        raise refuse(f"more than one constraint a pod ({len(constraints)} topologySpreadConstraints)")
    c = constraints[0]
    if c.when_unsatisfiable != "DoNotSchedule":
        raise refuse(f"whenUnsatisfiable: {c.when_unsatisfiable} (the scoring half)")
    if c.match_expressions:
        raise refuse("labelSelector.matchExpressions")
    if c.min_domains is not None:
        raise refuse("minDomains")
    if c.match_label_keys:
        raise refuse("matchLabelKeys")
    if not c.topology_key:
        raise refuse("a constraint without topologyKey")
    if c.max_skew < 1:
        raise refuse(f"maxSkew {c.max_skew} (upstream requires at least 1)")
    return c


def selector_matches(match_labels: Dict[str, str], labels: Dict[str, str]) -> bool:
    """matchLabels: every pair present (an empty selector matches every pod)."""
    return all(labels.get(k) == v for k, v in match_labels.items())


class PodTopologySpread(FilterPlugin):
    """kube-scheduler's PodTopologySpread, the Filter half with
    `whenUnsatisfiable: DoNotSchedule` (upstream pkg/scheduler/framework/
    plugins/podtopologyspread). For a pod `p` carrying one constraint
    `(maxSkew, topologyKey, selector)`:

    - `D`: the values of `topologyKey` over the nodes in the scheduler's
      cache that carry the key. Resource fit plays no part in `D`: a zone
      whose nodes are all full still holds the minimum down (the pod then
      waits, as upstream's does). `minDomains` unset. Every node with the
      key counts, whatever its taints: a pod that carries a constraint AND a
      nodeSelector, a node affinity or a toleration is refused by name
      (upstream's `nodeAffinityPolicy` / `nodeTaintsPolicy` decide which
      nodes count for such a pod's skew; not modelled).
    - `match(d)`: the pods in the scheduler's cache (assigned and not yet
      known to have left: `Scheduler.assignments`) on nodes of domain `d`
      whose labels satisfy `selector` (`matchLabels`, one namespace).
    - `self`: 1 if `p`'s own labels satisfy `selector`, else 0.
      `minMatch = min over d in D of match(d)`.
    - Node `n` passes iff it carries the key and
      `match(domain(n)) + self - minMatch <= maxSkew`. A node without the
      key fails.

    A pod without constraints passes every node. The filter ANDs into the
    chain like any other; scoring, the last-max-wins tie-break and the
    unschedulable queue's wake rules are unchanged. What it does not
    implement it refuses by name (`supported_spread_constraint`)."""

    def filter(self, pod: Pod, nodes: List[Node], cache: SchedulerCache) -> List[Node]:
        constraint = supported_spread_constraint(pod)
        if constraint is None:
            return nodes
        key = constraint.topology_key
        match: Dict[str, int] = {}
        for name, node in cache.nodes.items():
            domain = node.metadata.labels.get(key)
            if domain is None:
                continue
            match.setdefault(domain, 0)
            for pod_name in cache.assignments.get(name, ()):
                placed = cache.pods.get(pod_name)
                if placed is not None and selector_matches(
                    constraint.match_labels, placed.metadata.labels
                ):
                    match[domain] += 1
        if not match:
            return []
        own = int(selector_matches(constraint.match_labels, pod.metadata.labels))
        least = min(match.values())
        return [
            node
            for node in nodes
            if key in node.metadata.labels
            and match[node.metadata.labels[key]] + own - least <= constraint.max_skew
        ]


class UnsupportedNodePlacement(ValueError):
    """A pod's node affinity or tolerations, or a node's taints, say
    something the NodeAffinity and TaintToleration filters do not implement.
    Raised where it is used, naming the part: never ignored."""


# A node selector expression as both filters' callers hold it: (key,
# operator, values), hashable, values sorted. A term is a tuple of them.
Expression = Tuple[str, str, Tuple[str, ...]]
NODE_SELECTOR_OPERATORS = ("In", "NotIn", "Exists", "DoesNotExist")


def _refuse_placement(pod: Pod, what: str) -> UnsupportedNodePlacement:
    return UnsupportedNodePlacement(
        f"pod {pod.metadata.name!r}: {what} is not supported: NodeAffinity implements nodeSelector and "
        "required and preferred terms of matchExpressions (In, NotIn, Exists, DoesNotExist), "
        "TaintToleration the effects NoSchedule and PreferNoSchedule"
    )


def _term_expressions(pod: Pod, term) -> Tuple[Expression, ...]:
    """One nodeSelectorTerm's expressions, hashable; raises naming what is
    refused."""
    if term.match_fields:
        raise _refuse_placement(pod, "matchFields")
    if not term.match_expressions:
        raise _refuse_placement(pod, "a nodeSelectorTerm without matchExpressions")
    expressions = []
    for e in term.match_expressions:
        if e.operator not in NODE_SELECTOR_OPERATORS:
            raise _refuse_placement(pod, f"the node selector operator {e.operator}")
        if e.operator in ("In", "NotIn") and not e.values:
            raise _refuse_placement(pod, f"{e.operator} without values")
        values = tuple(sorted(e.values)) if e.operator in ("In", "NotIn") else ()
        expressions.append((e.key, e.operator, values))
    return tuple(expressions)


def _refuse_with_spread(pod: Pod) -> None:
    if pod.spec.topology_spread_constraints and pod.spec.names_nodes():
        supported_spread_constraint(pod)  # raises, naming the pair


def supported_node_terms(pod: Pod) -> Optional[Tuple[Tuple[Expression, ...], ...]]:
    """The pod's node selector terms as NodeAffinity implements them, None
    for a pod with neither a nodeSelector nor a node affinity (it passes
    every node): the required terms, ORed, each a tuple of expressions,
    ANDed, with the nodeSelector's pairs (`key In [value]`) in every one of
    them. Raises UnsupportedNodePlacement naming what is refused. The scalar
    filter and the batched trace compiler both call this."""
    _refuse_with_spread(pod)
    affinity = pod.spec.node_affinity
    selector = tuple(
        (key, "In", (value,)) for key, value in sorted(pod.spec.node_selector.items())
    )
    if affinity is None or not affinity.has_required:
        return (selector,) if selector else None
    if not affinity.required_terms:
        raise _refuse_placement(pod, "a node affinity without nodeSelectorTerms")
    return tuple(selector + _term_expressions(pod, term) for term in affinity.required_terms)


def supported_preferred_terms(pod: Pod) -> Tuple[Tuple[int, Tuple[Expression, ...]], ...]:
    """The pod's preferredDuringSchedulingIgnoredDuringExecution terms as
    NodeAffinity scores them: (weight, expressions) pairs, () for a pod with
    none. Raises UnsupportedNodePlacement naming what is refused."""
    affinity = pod.spec.node_affinity
    if affinity is None or not affinity.preferred:
        return ()
    _refuse_with_spread(pod)
    out = []
    for preferred in affinity.preferred:
        if not 1 <= preferred.weight <= MAX_NODE_SCORE:
            raise _refuse_placement(pod, f"a preferred term of weight {preferred.weight} (upstream: 1-100)")
        out.append((int(preferred.weight), _term_expressions(pod, preferred.preference)))
    return tuple(out)


def expression_matches(expression: Expression, labels: Dict[str, str]) -> bool:
    key, operator, values = expression
    if operator == "In":
        return key in labels and labels[key] in values
    if operator == "NotIn":
        return key not in labels or labels[key] not in values
    if operator == "Exists":
        return key in labels
    return key not in labels  # DoesNotExist


def node_taints(node: Node, effect: str = "NoSchedule") -> Tuple[Tuple[str, str], ...]:
    """The node's taints of `effect` (NoSchedule: the filter half's;
    PreferNoSchedule: the score half's) as (key, value) pairs; raises
    UnsupportedNodePlacement naming any third effect."""
    out = []
    for taint in node.spec.taints:
        if taint.effect not in ("NoSchedule", "PreferNoSchedule"):
            raise UnsupportedNodePlacement(
                f"node {node.metadata.name!r}: the taint effect {taint.effect} is not supported "
                "(NoExecute: eviction is not modelled): TaintToleration implements NoSchedule and "
                "PreferNoSchedule"
            )
        if taint.effect == effect:
            out.append((taint.key, taint.value))
    return tuple(out)


def unscored_preferred_term(pod_name: str) -> UnsupportedNodePlacement:
    return UnsupportedNodePlacement(
        f"pod {pod_name!r}: preferredDuringSchedulingIgnoredDuringExecution (the scoring half) under a "
        "profile that does not score by NodeAffinity: the preference would be ignored (kube_default "
        "scores by it)"
    )


def unscored_soft_taint(node_name: str) -> UnsupportedNodePlacement:
    return UnsupportedNodePlacement(
        f"node {node_name!r}: the taint effect PreferNoSchedule (the scoring half) under a profile that "
        "does not score by TaintToleration: the preference would be ignored (kube_default scores by it)"
    )


def ignores_preferences(filtered, scored) -> Tuple[bool, bool]:
    """(preferred terms, PreferNoSchedule taints): which of the two a profile
    of these filter and score plugin names would silently ignore, because it
    filters by the plugin and does not score by it. Both paths refuse a pod or
    a node that carries one under such a profile, by name."""
    return (
        NODE_AFFINITY in filtered and NODE_AFFINITY not in scored,
        TAINT_TOLERATION in filtered and TAINT_TOLERATION not in scored,
    )


def supported_tolerations(pod: Pod) -> Tuple[Toleration, ...]:
    """The pod's tolerations as TaintToleration implements them; raises
    UnsupportedNodePlacement naming what is refused."""
    _refuse_with_spread(pod)
    for t in pod.spec.tolerations:
        if t.operator not in ("Equal", "Exists"):
            raise _refuse_placement(pod, f"the toleration operator {t.operator}")
        if t.effect not in ("", "NoSchedule", "PreferNoSchedule"):
            raise _refuse_placement(pod, f"a toleration of the effect {t.effect}")
        if not t.key and t.operator != "Exists":
            raise _refuse_placement(pod, "a toleration with an empty key and the operator Equal")
    return tuple(pod.spec.tolerations)


def tolerates(tolerations, taint: Tuple[str, str], effect: str = "NoSchedule") -> bool:
    """Whether one of `tolerations` matches the taint (key, value) of
    `effect` (a toleration's empty effect matches every effect)."""
    key, value = taint
    for t in tolerations:
        if t.effect not in ("", effect):
            continue
        if t.operator == "Exists":
            if not t.key or t.key == key:
                return True
        elif t.key == key and t.value == value:
            return True
    return False


class NodeAffinity(FilterPlugin, ScorePlugin):
    """kube-scheduler's NodeAffinity. The Filter half (docs/PARITY.md "Node
    affinity and taints"): a node passes iff its labels carry every pair of
    the pod's `nodeSelector` AND satisfy at least one of its required
    `nodeSelectorTerms`, a term being the AND of its `matchExpressions` (In,
    NotIn, Exists, DoesNotExist on `metadata.labels`). A pod with neither
    passes every node. The Score half ("Scoring as kube-scheduler scores"):
    the sum of the weights of the pod's preferred terms the node's labels
    match, as its share of the largest sum among the nodes that passed the
    filters. What it does not implement it refuses by name
    (`supported_node_terms`, `supported_preferred_terms`)."""

    def score(self, pod: Pod, node: Node) -> int:
        labels = node.metadata.labels
        return sum(
            weight
            for weight, term in supported_preferred_terms(pod)
            if all(expression_matches(e, labels) for e in term)
        )

    def normalize(self, scores: List[int]) -> List[int]:
        return normalize_by_max(scores, reverse=False)

    def filter(self, pod: Pod, nodes: List[Node], cache: SchedulerCache) -> List[Node]:
        terms = supported_node_terms(pod)
        if terms is None:
            return nodes
        return [
            node
            for node in nodes
            if any(all(expression_matches(e, node.metadata.labels) for e in term) for term in terms)
        ]


class TaintToleration(FilterPlugin, ScorePlugin):
    """kube-scheduler's TaintToleration. The Filter half (docs/PARITY.md
    "Node affinity and taints"): a node passes iff each of its taints of
    effect NoSchedule is tolerated by the pod. A toleration matches a taint
    on `key` and `operator` (`Equal`: the value too; `Exists`: the key, or
    every taint where the key is empty) and `effect` (empty matches every
    effect). The Score half ("Scoring as kube-scheduler scores"): how many of
    the node's PreferNoSchedule taints the pod does not tolerate, reversed
    against the largest count among the nodes that passed the filters.
    NoExecute is refused by name (`node_taints`)."""

    def score(self, pod: Pod, node: Node) -> int:
        tolerations = supported_tolerations(pod)
        return sum(
            not tolerates(tolerations, t, "PreferNoSchedule")
            for t in node_taints(node, "PreferNoSchedule")
        )

    def normalize(self, scores: List[int]) -> List[int]:
        return normalize_by_max(scores, reverse=True)

    def filter(self, pod: Pod, nodes: List[Node], cache: SchedulerCache) -> List[Node]:
        tolerations = supported_tolerations(pod)
        return [
            node for node in nodes if all(tolerates(tolerations, t) for t in node_taints(node))
        ]


PLUGIN_REGISTRY: Dict[str, Union[FilterPlugin, ScorePlugin]] = {
    FIT: Fit(),
    TOPOLOGY_SPREAD: PodTopologySpread(),
    NODE_AFFINITY: NodeAffinity(),
    TAINT_TOLERATION: TaintToleration(),
    LEAST_ALLOCATED: LeastAllocatedResources(),
    MOST_ALLOCATED: MostAllocatedResources(),
    BALANCED: BalancedResourceAllocation(),
    NODE_RESOURCES_FIT: NodeResourcesFit(),
    BALANCED_ALLOCATION: NodeResourcesBalancedAllocation(),
}


def register_plugin(name: str, plugin: Union[FilterPlugin, ScorePlugin]) -> None:
    """Extension point for custom plugins (the reference's registry is a static
    map; here plugins may be registered at runtime). A runtime-registered
    plugin runs on the SCALAR path only — the batched engine refuses profiles
    it cannot lower (batched/pipeline.py) instead of silently substituting
    the default."""
    PLUGIN_REGISTRY[name] = plugin
