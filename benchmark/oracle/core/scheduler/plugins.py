"""Scheduler plugin registry with Fit and LeastAllocatedResources built-ins
(reference: src/core/scheduler/plugin.rs), extended with the packing-side
scorers the batched device pipeline also lowers (MostAllocatedResources,
BalancedResourceAllocation).

The plugin NAME constants below are the shared vocabulary between this
scalar registry and the device-plugin registry in
kubernetriks_tpu/batched/pipeline.py: a profile referencing these names runs
on both paths with one definition of the semantics (the batched registry
validates against them at engine construction and raises loudly on a name it
cannot lower)."""

from __future__ import annotations

from typing import Dict, List, Union

from benchmark.oracle.core.types import Node, Pod

# Shared plugin-name constants (scalar registry keys == device registry keys).
FIT = "Fit"
LEAST_ALLOCATED = "LeastAllocatedResources"
MOST_ALLOCATED = "MostAllocatedResources"
BALANCED = "BalancedResourceAllocation"


class FilterPlugin:
    def filter(self, pod: Pod, nodes: List[Node]) -> List[Node]:
        raise NotImplementedError


class ScorePlugin:
    def score(self, pod: Pod, node: Node) -> float:
        raise NotImplementedError


class Fit(FilterPlugin):
    """Keep nodes whose allocatable covers the pod's requests
    (reference: src/core/scheduler/plugin.rs:33-45)."""

    def filter(self, pod: Pod, nodes: List[Node]) -> List[Node]:
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


PLUGIN_REGISTRY: Dict[str, Union[FilterPlugin, ScorePlugin]] = {
    FIT: Fit(),
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
