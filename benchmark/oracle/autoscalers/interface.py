"""Autoscaler interfaces and info payloads.

Mirrors the reference's autoscaler interface modules (reference:
src/autoscalers/cluster_autoscaler/interface.rs,
src/autoscalers/horizontal_pod_autoscaler/interface.rs).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set

from benchmark.oracle.core.types import (
    Node,
    Pod,
    RuntimeResourcesUsageModelConfig,
)

# Label value marking nodes created by the cluster autoscaler; shared by the
# CA (labeling), storage (scale-down info filter) and scale-down matching
# (reference: src/autoscalers/cluster_autoscaler/kube_cluster_autoscaler.rs:13).
CLUSTER_AUTOSCALER_ORIGIN_LABEL = "cluster autoscaler"


# --- cluster autoscaler -----------------------------------------------------


@dataclass
class CaNodeGroup:
    """Cluster-autoscaler node group state
    (reference: src/autoscalers/cluster_autoscaler/interface.rs:7-18)."""

    node_template: Node
    # Max simultaneous nodes for this group; None = bounded only by the global
    # max_node_count.
    max_count: Optional[int] = None
    current_count: int = 0
    # Monotonic counter for unique scaled-up node names.
    total_allocated: int = 0


@dataclass
class ScaleUpNodeAction:
    node: Node


@dataclass
class ScaleDownNodeAction:
    node_name: str


@dataclass
class ScaleUpInfo:
    """reference: src/autoscalers/cluster_autoscaler/interface.rs:26-29."""

    unscheduled_pods: List[Pod]


@dataclass
class ScaleDownInfo:
    """reference: src/autoscalers/cluster_autoscaler/interface.rs:32-41."""

    nodes: List[Node]
    pods_on_autoscaled_nodes: Dict[str, Pod]
    assignments: Dict[str, Set[str]]


@dataclass
class AutoscaleInfo:
    scale_up: Optional[ScaleUpInfo] = None
    scale_down: Optional[ScaleDownInfo] = None


class AutoscaleInfoRequestType(enum.Enum):
    """reference: src/autoscalers/cluster_autoscaler/interface.rs:48-58."""

    AUTO = "Auto"
    SCALE_UP_ONLY = "ScaleUpOnly"
    SCALE_DOWN_ONLY = "ScaleDownOnly"
    BOTH = "Both"


class ClusterAutoscalerAlgorithm:
    """reference: src/autoscalers/cluster_autoscaler/interface.rs:60-68."""

    def info_request_type(self) -> AutoscaleInfoRequestType:
        raise NotImplementedError

    def autoscale(
        self,
        info: AutoscaleInfo,
        node_groups: Dict[str, CaNodeGroup],
        max_node_count: int,
    ) -> List[Any]:
        raise NotImplementedError


# --- horizontal pod autoscaler ----------------------------------------------


@dataclass
class TargetResourcesUsage:
    """Target cpu/ram utilization ratios in [0,1], relative to requests
    (reference: src/autoscalers/horizontal_pod_autoscaler/interface.rs:10-14)."""

    cpu_utilization: Optional[float] = None
    ram_utilization: Optional[float] = None

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "TargetResourcesUsage":
        if not d:
            return TargetResourcesUsage()
        return TargetResourcesUsage(
            cpu_utilization=d.get("cpu_utilization"),
            ram_utilization=d.get("ram_utilization"),
        )


@dataclass
class PodGroup:
    """A set of long-running service pods scaled together
    (reference: src/autoscalers/horizontal_pod_autoscaler/interface.rs:19-34)."""

    name: str
    initial_pod_count: int
    max_pod_count: int
    pod_template: Pod
    target_resources_usage: TargetResourcesUsage
    resources_usage_model_config: Optional[RuntimeResourcesUsageModelConfig]

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "PodGroup":
        return PodGroup(
            name=d.get("name", ""),
            initial_pod_count=int(d.get("initial_pod_count", 0)),
            max_pod_count=int(d.get("max_pod_count", 0)),
            pod_template=Pod.from_dict(d.get("pod_template") or {}),
            target_resources_usage=TargetResourcesUsage.from_dict(
                d.get("target_resources_usage")
            ),
            resources_usage_model_config=RuntimeResourcesUsageModelConfig.from_dict(
                d.get("resources_usage_model_config")
            ),
        )


@dataclass
class PodGroupInfo:
    """reference: src/autoscalers/horizontal_pod_autoscaler/interface.rs:37-46."""

    creation_time: float
    pod_group: PodGroup
    created_pods: Set[str] = field(default_factory=set)
    total_created: int = 0


@dataclass
class ScaleUpPodAction:
    pod: Pod


@dataclass
class ScaleDownPodAction:
    pod_name: str


class HorizontalPodAutoscalerAlgorithm:
    """reference: src/autoscalers/horizontal_pod_autoscaler/interface.rs:53-59."""

    def autoscale(
        self, pod_group_metrics, pod_group_info: PodGroupInfo
    ) -> List[Any]:
        raise NotImplementedError
