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
class Node:
    """reference: src/core/node.rs:44-51."""

    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    status: NodeStatus = field(default_factory=NodeStatus)

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
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "metadata": self.metadata.to_dict(),
            "status": {
                "allocatable": self.status.allocatable.to_dict(),
                "capacity": self.status.capacity.to_dict(),
            },
        }


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
_SPREAD_KEYS = {
    "maxSkew": "max_skew",
    "topologyKey": "topology_key",
    "whenUnsatisfiable": "when_unsatisfiable",
    "labelSelector": "label_selector",
    "matchLabels": "match_labels",
    "matchExpressions": "match_expressions",
    "minDomains": "min_domains",
    "matchLabelKeys": "match_label_keys",
}


def _snake(d: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    return {_SPREAD_KEYS.get(k, k): v for k, v in (d or {}).items()}


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
class PodSpec:
    """running_duration=None means an infinitely long-running service
    (reference: src/core/pod.rs:16-23)."""

    resources: Resources = field(default_factory=Resources)
    running_duration: Optional[float] = None
    # The scheduler's PodTopologySpread filter reads these against the
    # nodes' and the placed pods' metadata.labels; immutable once parsed
    # (copies share the list).
    topology_spread_constraints: List[TopologySpreadConstraint] = field(default_factory=list)


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
