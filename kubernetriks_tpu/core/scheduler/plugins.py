"""Scheduler plugin registry: the reference's Fit and LeastAllocatedResources
(reference: src/core/scheduler/plugin.rs), the packing-side scorers
(MostAllocatedResources, BalancedResourceAllocation) and kube-scheduler's
PodTopologySpread filter (DoNotSchedule). The batched device pipeline lowers
every one of them.

A filter sees the pod, the nodes still in the running and the scheduler's
cache (`SchedulerCache`: every cached node, the cached pods, and which pods
the scheduler has assigned to which node); a scorer sees one pod and one node.

The plugin NAME constants below are the shared vocabulary between this
scalar registry and the device-plugin registry in
kubernetriks_tpu/batched/pipeline.py: a profile referencing these names runs
on both paths with one definition of the semantics (the batched registry
validates against them at engine construction and raises loudly on a name it
cannot lower)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Union

from kubernetriks_tpu.core.types import Node, Pod, TopologySpreadConstraint

# Shared plugin-name constants (scalar registry keys == device registry keys).
FIT = "Fit"
LEAST_ALLOCATED = "LeastAllocatedResources"
MOST_ALLOCATED = "MostAllocatedResources"
BALANCED = "BalancedResourceAllocation"
TOPOLOGY_SPREAD = "PodTopologySpread"


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
      waits, as upstream's does). No node affinity, no taints, `minDomains`
      unset.
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


PLUGIN_REGISTRY: Dict[str, Union[FilterPlugin, ScorePlugin]] = {
    FIT: Fit(),
    TOPOLOGY_SPREAD: PodTopologySpread(),
    LEAST_ALLOCATED: LeastAllocatedResources(),
    MOST_ALLOCATED: MostAllocatedResources(),
    BALANCED: BalancedResourceAllocation(),
}


def register_plugin(name: str, plugin: Union[FilterPlugin, ScorePlugin]) -> None:
    """Extension point for custom plugins (the reference's registry is a static
    map; here plugins may be registered at runtime). A runtime-registered
    plugin runs on the SCALAR path only — the batched engine refuses profiles
    it cannot lower (batched/pipeline.py) instead of silently substituting
    the default."""
    PLUGIN_REGISTRY[name] = plugin
