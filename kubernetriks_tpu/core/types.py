"""Kubernetes object model for the simulated control plane.

Mirrors the reference's object model (reference: src/core/common.rs:33-65,
src/core/node.rs:7-94, src/core/pod.rs:7-123) — a pared-down k8s API surface:
ObjectMeta, RuntimeResources (cpu millicores / ram bytes), Node with
capacity/allocatable/conditions, Pod with requests/limits/duration/conditions.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class RuntimeResources:
    """cpu in millicores, ram in bytes (reference: src/core/common.rs:47-51)."""

    cpu: int = 0
    ram: int = 0

    def copy(self) -> "RuntimeResources":
        return RuntimeResources(self.cpu, self.ram)

    def __add__(self, other: "RuntimeResources") -> "RuntimeResources":
        return RuntimeResources(self.cpu + other.cpu, self.ram + other.ram)

    def __sub__(self, other: "RuntimeResources") -> "RuntimeResources":
        return RuntimeResources(self.cpu - other.cpu, self.ram - other.ram)

    def fits(self, requests: "RuntimeResources") -> bool:
        return requests.cpu <= self.cpu and requests.ram <= self.ram

    def is_zero(self) -> bool:
        return self.cpu == 0 and self.ram == 0

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "RuntimeResources":
        if not d:
            return RuntimeResources()
        return RuntimeResources(cpu=int(d.get("cpu", 0)), ram=int(d.get("ram", 0)))

    def to_dict(self) -> Dict[str, Any]:
        return {"cpu": self.cpu, "ram": self.ram}


@dataclass
class ObjectMeta:
    """Partial k8s ObjectMeta (reference: src/core/common.rs:33-45)."""

    name: str = ""
    labels: Dict[str, str] = field(default_factory=dict)
    creation_timestamp: float = 0.0

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "ObjectMeta":
        if not d:
            return ObjectMeta()
        return ObjectMeta(
            name=d.get("name", ""),
            labels=dict(d.get("labels") or {}),
            creation_timestamp=float(d.get("creation_timestamp", 0.0)),
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "labels": dict(self.labels),
            "creation_timestamp": self.creation_timestamp,
        }


class NodeConditionType(str, enum.Enum):
    """reference: src/core/node.rs:13-22."""

    NODE_CREATED = "NodeCreated"
    NODE_READY = "NodeReady"
    NODE_FAILED = "NodeFailed"
    NODE_REMOVED = "NodeRemoved"
    DISK_PRESSURE = "DiskPressure"
    MEMORY_PRESSURE = "MemoryPressure"
    PID_PRESSURE = "PIDPressure"


class PodConditionType(str, enum.Enum):
    """reference: src/core/pod.rs:25-44."""

    POD_CREATED = "PodCreated"
    POD_SCHEDULED = "PodScheduled"
    POD_INITIALIZING = "PodInitializing"
    POD_RUNNING = "PodRunning"
    POD_SUCCEEDED = "PodSucceeded"
    POD_FAILED = "PodFailed"
    POD_REMOVED = "PodRemoved"


@dataclass
class Condition:
    """Shared shape of Node/Pod conditions: status is "True"/"False"/"Unknown"."""

    status: str
    condition_type: Any  # NodeConditionType | PodConditionType
    last_transition_time: float


def _update_condition(
    conditions: List[Condition], status: str, condition_type: Any, time: float
) -> None:
    """Upsert semantics shared by Node and Pod (reference: src/core/node.rs:71-94)."""
    for cond in conditions:
        if cond.condition_type == condition_type:
            cond.status = status
            cond.last_transition_time = time
            return
    conditions.append(Condition(status, condition_type, time))


@dataclass
class NodeStatus:
    allocatable: RuntimeResources = field(default_factory=RuntimeResources)
    capacity: RuntimeResources = field(default_factory=RuntimeResources)
    conditions: List[Condition] = field(default_factory=list)


@dataclass
class Taint:
    """One entry of a node's `spec.taints`. Every effect upstream has is kept,
    so that what the scheduler does not implement is refused by name where it
    is used (core/scheduler/plugins.node_taints), never dropped at parse."""

    key: str = ""
    value: str = ""
    effect: str = "NoSchedule"

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Taint":
        return Taint(
            key=str(d.get("key", "")),
            value=str(d.get("value") or ""),
            effect=str(d.get("effect", "NoSchedule")),
        )

    def to_dict(self) -> Dict[str, Any]:
        return {"key": self.key, "value": self.value, "effect": self.effect}


@dataclass
class NodeSpec:
    # The scheduler's TaintToleration filter reads these; immutable once
    # parsed (copies share the list).
    taints: List[Taint] = field(default_factory=list)


@dataclass
class Node:
    """reference: src/core/node.rs:44-51."""

    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    status: NodeStatus = field(default_factory=NodeStatus)
    spec: NodeSpec = field(default_factory=NodeSpec)

    @staticmethod
    def new(name: str, cpu: int, ram: int) -> "Node":
        return Node(
            metadata=ObjectMeta(name=name),
            status=NodeStatus(
                allocatable=RuntimeResources(cpu, ram),
                capacity=RuntimeResources(cpu, ram),
            ),
        )

    def update_condition(
        self, status: str, condition_type: NodeConditionType, time: float
    ) -> None:
        _update_condition(self.status.conditions, status, condition_type, time)

    def get_condition(self, condition_type: NodeConditionType) -> Optional[Condition]:
        for cond in self.status.conditions:
            if cond.condition_type == condition_type:
                return cond
        return None

    def copy(self) -> "Node":
        node = Node(
            metadata=ObjectMeta(
                self.metadata.name,
                dict(self.metadata.labels),
                self.metadata.creation_timestamp,
            ),
            status=NodeStatus(
                allocatable=self.status.allocatable.copy(),
                capacity=self.status.capacity.copy(),
                conditions=[
                    Condition(c.status, c.condition_type, c.last_transition_time)
                    for c in self.status.conditions
                ],
            ),
            spec=NodeSpec(taints=self.spec.taints),
        )
        return node

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Node":
        """Missing allocatable defaults to capacity — node templates in configs
        and traces specify only capacity; the reference re-establishes
        allocatable=capacity at every template consumer (e.g.
        src/trace/generic.rs:98, cluster_autoscaler.rs:111); here it is
        normalized once at parse time."""
        status = d.get("status") or {}
        capacity = RuntimeResources.from_dict(status.get("capacity"))
        allocatable_raw = status.get("allocatable")
        allocatable = (
            RuntimeResources.from_dict(allocatable_raw)
            if allocatable_raw
            else capacity.copy()
        )
        return Node(
            metadata=ObjectMeta.from_dict(d.get("metadata")),
            status=NodeStatus(allocatable=allocatable, capacity=capacity),
            spec=NodeSpec(
                taints=[Taint.from_dict(t) for t in (d.get("spec") or {}).get("taints") or []]
            ),
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "metadata": self.metadata.to_dict(),
            "status": {
                "allocatable": self.status.allocatable.to_dict(),
                "capacity": self.status.capacity.to_dict(),
            },
        }
        if self.spec.taints:
            out["spec"] = {"taints": [t.to_dict() for t in self.spec.taints]}
        return out


@dataclass
class ResourceUsageModelConfig:
    """Nested YAML-in-string model config (reference: src/core/resource_usage/interface.rs:13-18)."""

    model_name: str = ""
    config: str = ""

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> Optional["ResourceUsageModelConfig"]:
        if not d:
            return None
        return ResourceUsageModelConfig(
            model_name=d.get("model_name", ""), config=d.get("config", "")
        )

    def to_dict(self) -> Dict[str, Any]:
        return {"model_name": self.model_name, "config": self.config}


@dataclass
class RuntimeResourcesUsageModelConfig:
    """reference: src/core/common.rs:54-57."""

    cpu_config: Optional[ResourceUsageModelConfig] = None
    ram_config: Optional[ResourceUsageModelConfig] = None

    @staticmethod
    def from_dict(
        d: Optional[Dict[str, Any]],
    ) -> Optional["RuntimeResourcesUsageModelConfig"]:
        if not d:
            return None
        return RuntimeResourcesUsageModelConfig(
            cpu_config=ResourceUsageModelConfig.from_dict(d.get("cpu_config")),
            ram_config=ResourceUsageModelConfig.from_dict(d.get("ram_config")),
        )


@dataclass
class Resources:
    """reference: src/core/pod.rs:8-14."""

    limits: RuntimeResources = field(default_factory=RuntimeResources)
    requests: RuntimeResources = field(default_factory=RuntimeResources)
    usage_model_config: Optional[RuntimeResourcesUsageModelConfig] = None


# Upstream spells these keys in camelCase, the trace vocabulary here in
# snake_case; both are read, snake_case is written.
_SNAKE_KEYS = {
    "maxSkew": "max_skew",
    "topologyKey": "topology_key",
    "whenUnsatisfiable": "when_unsatisfiable",
    "labelSelector": "label_selector",
    "matchLabels": "match_labels",
    "matchExpressions": "match_expressions",
    "minDomains": "min_domains",
    "matchLabelKeys": "match_label_keys",
    "nodeSelector": "node_selector",
    "nodeAffinity": "node_affinity",
    "requiredDuringSchedulingIgnoredDuringExecution": "required",
    "required_during_scheduling_ignored_during_execution": "required",
    "preferredDuringSchedulingIgnoredDuringExecution": "preferred",
    "preferred_during_scheduling_ignored_during_execution": "preferred",
    "nodeSelectorTerms": "node_selector_terms",
    "matchFields": "match_fields",
    "tolerationSeconds": "toleration_seconds",
}


def _snake(d: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    return {_SNAKE_KEYS.get(k, k): v for k, v in (d or {}).items()}


@dataclass
class TopologySpreadConstraint:
    """One entry of a pod's `spec.topology_spread_constraints` (upstream
    `topologySpreadConstraints`). Everything upstream's entry can say is
    kept, so that what the scheduler does not implement is REFUSED by name
    where it is used (core/scheduler/plugins.supported_spread_constraint),
    never dropped at parse."""

    max_skew: int = 1
    topology_key: str = ""
    when_unsatisfiable: str = "DoNotSchedule"
    match_labels: Dict[str, str] = field(default_factory=dict)
    match_expressions: List[Any] = field(default_factory=list)
    min_domains: Optional[int] = None
    match_label_keys: List[str] = field(default_factory=list)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "TopologySpreadConstraint":
        d = _snake(d)
        selector = _snake(d.get("label_selector"))
        return TopologySpreadConstraint(
            max_skew=int(d.get("max_skew", 1)),
            topology_key=str(d.get("topology_key", "")),
            when_unsatisfiable=str(d.get("when_unsatisfiable", "DoNotSchedule")),
            match_labels={str(k): str(v) for k, v in (selector.get("match_labels") or {}).items()},
            match_expressions=list(selector.get("match_expressions") or []),
            min_domains=d.get("min_domains"),
            match_label_keys=list(d.get("match_label_keys") or []),
        )

    def to_dict(self) -> Dict[str, Any]:
        selector: Dict[str, Any] = {"match_labels": dict(self.match_labels)}
        if self.match_expressions:
            selector["match_expressions"] = list(self.match_expressions)
        out: Dict[str, Any] = {
            "max_skew": self.max_skew,
            "topology_key": self.topology_key,
            "when_unsatisfiable": self.when_unsatisfiable,
            "label_selector": selector,
        }
        if self.min_domains is not None:
            out["min_domains"] = self.min_domains
        if self.match_label_keys:
            out["match_label_keys"] = list(self.match_label_keys)
        return out


@dataclass
class NodeSelectorRequirement:
    """One `matchExpressions` entry of a node selector term: `operator` over
    the node's `metadata.labels[key]` and `values`, as upstream spells it."""

    key: str = ""
    operator: str = "In"
    values: List[str] = field(default_factory=list)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "NodeSelectorRequirement":
        return NodeSelectorRequirement(
            key=str(d.get("key", "")),
            operator=str(d.get("operator", "In")),
            values=[str(v) for v in d.get("values") or []],
        )

    def to_dict(self) -> Dict[str, Any]:
        return {"key": self.key, "operator": self.operator, "values": list(self.values)}


@dataclass
class NodeSelectorTerm:
    match_expressions: List[NodeSelectorRequirement] = field(default_factory=list)
    match_fields: List[Any] = field(default_factory=list)


    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "NodeSelectorTerm":
        d = _snake(d)
        return NodeSelectorTerm(
            match_expressions=[
                NodeSelectorRequirement.from_dict(e) for e in d.get("match_expressions") or []
            ],
            match_fields=list(d.get("match_fields") or []),
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"match_expressions": [e.to_dict() for e in self.match_expressions]}
        if self.match_fields:
            out["match_fields"] = list(self.match_fields)
        return out


@dataclass
class PreferredSchedulingTerm:
    """One entry of `preferredDuringSchedulingIgnoredDuringExecution`: a
    weight (upstream: 1-100) and the nodeSelectorTerm it is given for."""

    weight: int = 1
    preference: NodeSelectorTerm = field(default_factory=NodeSelectorTerm)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "PreferredSchedulingTerm":
        return PreferredSchedulingTerm(
            weight=int(d.get("weight", 1)),
            preference=NodeSelectorTerm.from_dict(d.get("preference")),
        )

    def to_dict(self) -> Dict[str, Any]:
        return {"weight": self.weight, "preference": self.preference.to_dict()}


@dataclass
class NodeAffinity:
    """A pod's `spec.affinity.nodeAffinity`: the required terms (ORed; a
    term's expressions ANDed; `has_required` says whether the pod states that
    half at all) and the preferred terms, each with its weight."""

    required_terms: List[NodeSelectorTerm] = field(default_factory=list)
    preferred: List[PreferredSchedulingTerm] = field(default_factory=list)
    has_required: bool = True

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> Optional["NodeAffinity"]:
        if not d:
            return None
        d = _snake(d)
        terms = _snake(d.get("required")).get("node_selector_terms") or []
        return NodeAffinity(
            required_terms=[NodeSelectorTerm.from_dict(t) for t in terms],
            preferred=[PreferredSchedulingTerm.from_dict(t) for t in d.get("preferred") or []],
            has_required="required" in d,
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        if self.has_required:
            out["required"] = {"node_selector_terms": [t.to_dict() for t in self.required_terms]}
        if self.preferred:
            out["preferred"] = [t.to_dict() for t in self.preferred]
        return out


@dataclass
class Toleration:
    """One entry of a pod's `spec.tolerations`, as upstream spells it."""

    key: str = ""
    operator: str = "Equal"
    value: str = ""
    effect: str = ""
    toleration_seconds: Optional[float] = None

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Toleration":
        d = _snake(d)
        return Toleration(
            key=str(d.get("key") or ""),
            operator=str(d.get("operator") or "Equal"),
            value=str(d.get("value") or ""),
            effect=str(d.get("effect") or ""),
            toleration_seconds=d.get("toleration_seconds"),
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "key": self.key, "operator": self.operator, "value": self.value, "effect": self.effect,
        }
        if self.toleration_seconds is not None:
            out["toleration_seconds"] = self.toleration_seconds
        return out


@dataclass
class PodSpec:
    """running_duration=None means an infinitely long-running service
    (reference: src/core/pod.rs:16-23)."""

    resources: Resources = field(default_factory=Resources)
    running_duration: Optional[float] = None
    # The scheduler's PodTopologySpread filter reads these against the
    # nodes' and the placed pods' metadata.labels; immutable once parsed
    # (copies share the list).
    topology_spread_constraints: List[TopologySpreadConstraint] = field(default_factory=list)
    # What the NodeAffinity and TaintToleration filters read against a
    # node's metadata.labels and spec.taints; immutable once parsed (copies
    # share them).
    node_selector: Dict[str, str] = field(default_factory=dict)
    node_affinity: Optional[NodeAffinity] = None
    tolerations: List[Toleration] = field(default_factory=list)

    def names_nodes(self) -> bool:
        """Whether the pod carries a selector, an affinity or a toleration."""
        return bool(self.node_selector or self.node_affinity is not None or self.tolerations)


@dataclass
class PodStatus:
    start_time: float = 0.0
    conditions: List[Condition] = field(default_factory=list)
    assigned_node: str = ""


@dataclass
class Pod:
    """reference: src/core/pod.rs:62-68."""

    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    spec: PodSpec = field(default_factory=PodSpec)
    status: PodStatus = field(default_factory=PodStatus)

    @staticmethod
    def new(name: str, cpu: int, ram: int, running_duration: Optional[float]) -> "Pod":
        return Pod(
            metadata=ObjectMeta(name=name),
            spec=PodSpec(
                resources=Resources(
                    limits=RuntimeResources(cpu, ram),
                    requests=RuntimeResources(cpu, ram),
                ),
                running_duration=running_duration,
            ),
        )

    def update_condition(
        self, status: str, condition_type: PodConditionType, time: float
    ) -> None:
        _update_condition(self.status.conditions, status, condition_type, time)

    def get_condition(self, condition_type: PodConditionType) -> Optional[Condition]:
        for cond in self.status.conditions:
            if cond.condition_type == condition_type:
                return cond
        return None

    def copy(self) -> "Pod":
        return Pod(
            metadata=ObjectMeta(
                self.metadata.name,
                dict(self.metadata.labels),
                self.metadata.creation_timestamp,
            ),
            spec=PodSpec(
                resources=Resources(
                    limits=self.spec.resources.limits.copy(),
                    requests=self.spec.resources.requests.copy(),
                    usage_model_config=self.spec.resources.usage_model_config,
                ),
                running_duration=self.spec.running_duration,
                topology_spread_constraints=self.spec.topology_spread_constraints,
                node_selector=self.spec.node_selector,
                node_affinity=self.spec.node_affinity,
                tolerations=self.spec.tolerations,
            ),
            status=PodStatus(
                start_time=self.status.start_time,
                conditions=[
                    Condition(c.status, c.condition_type, c.last_transition_time)
                    for c in self.status.conditions
                ],
                assigned_node=self.status.assigned_node,
            ),
        )

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Pod":
        spec = d.get("spec") or {}
        resources = spec.get("resources") or {}
        placement = _snake(spec)
        affinity = _snake(placement.get("affinity"))
        return Pod(
            metadata=ObjectMeta.from_dict(d.get("metadata")),
            spec=PodSpec(
                resources=Resources(
                    limits=RuntimeResources.from_dict(resources.get("limits")),
                    requests=RuntimeResources.from_dict(resources.get("requests")),
                    usage_model_config=RuntimeResourcesUsageModelConfig.from_dict(
                        resources.get("usage_model_config")
                    ),
                ),
                running_duration=spec.get("running_duration"),
                topology_spread_constraints=[
                    TopologySpreadConstraint.from_dict(c)
                    for c in spec.get("topology_spread_constraints")
                    or spec.get("topologySpreadConstraints")
                    or []
                ],
                node_selector={
                    str(k): str(v) for k, v in (placement.get("node_selector") or {}).items()
                },
                node_affinity=NodeAffinity.from_dict(
                    affinity.get("node_affinity") or placement.get("node_affinity")
                ),
                tolerations=[Toleration.from_dict(t) for t in placement.get("tolerations") or []],
            ),
        )

    def to_dict(self) -> Dict[str, Any]:
        """The pod as the generic trace's `!CreatePod {pod: ...}` carries it
        (`from_dict` reads it back equal)."""
        resources: Dict[str, Any] = {
            "requests": self.spec.resources.requests.to_dict(),
            "limits": self.spec.resources.limits.to_dict(),
        }
        umc = self.spec.resources.usage_model_config
        if umc is not None:
            resources["usage_model_config"] = {
                key: cfg.to_dict()
                for key, cfg in (("cpu_config", umc.cpu_config), ("ram_config", umc.ram_config))
                if cfg is not None
            }
        spec: Dict[str, Any] = {"resources": resources}
        if self.spec.running_duration is not None:
            spec["running_duration"] = self.spec.running_duration
        if self.spec.topology_spread_constraints:
            spec["topology_spread_constraints"] = [
                c.to_dict() for c in self.spec.topology_spread_constraints
            ]
        if self.spec.node_selector:
            spec["node_selector"] = dict(self.spec.node_selector)
        if self.spec.node_affinity is not None:
            spec["affinity"] = {"node_affinity": self.spec.node_affinity.to_dict()}
        if self.spec.tolerations:
            spec["tolerations"] = [t.to_dict() for t in self.spec.tolerations]
        return {"metadata": self.metadata.to_dict(), "spec": spec}


@dataclass
class ObjectsInfo:
    """Name-keyed, sorted-iteration state maps (reference: src/core/common.rs:59-65).

    Python dicts preserve insertion order, not key order; components that rely on
    BTreeMap-sorted iteration must iterate via ``sorted_nodes``/``sorted_pods``.
    """

    nodes: Dict[str, Node] = field(default_factory=dict)
    pods: Dict[str, Pod] = field(default_factory=dict)

    def sorted_nodes(self) -> List[Node]:
        return [self.nodes[k] for k in sorted(self.nodes)]

    def sorted_pods(self) -> List[Pod]:
        return [self.pods[k] for k in sorted(self.pods)]
