"""Event vocabulary for the simulated control plane.

One dataclass per event, mirroring the reference's 30 event structs
(reference: src/core/events.rs:22-244). Python's dynamic dispatch replaces the
reference's `cast!`/`cast_box!` macros: components implement `on_<snake_case>`
methods and the kernel's EventHandler base routes by payload type.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from benchmark.oracle.core.types import (
    Node,
    Pod,
    PodConditionType,
    RuntimeResources,
    RuntimeResourcesUsageModelConfig,
)


# --- node lifecycle ---------------------------------------------------------


@dataclass
class CreateNodeRequest:
    """client/CA -> api server (reference: src/core/events.rs:22-25).
    recovered=True marks a chaos-engine recovery — the node returning after
    a crash as fresh capacity (kubernetriks_tpu/chaos.py); it flows the
    normal create chain and only adds fault accounting."""

    node: Node
    recovered: bool = False


@dataclass
class CreateNodeResponse:
    """persistent storage -> api server (reference: src/core/events.rs:29-32)."""

    node_name: str


@dataclass
class NodeAddedToCluster:
    """api server -> persistent storage (reference: src/core/events.rs:35-39)."""

    add_time: float
    node_name: str
    recovered: bool = False  # chaos-engine recovery (fault accounting only)


@dataclass
class RemoveNodeRequest:
    """client/CA -> api server; also api server -> node component
    (reference: src/core/events.rs:45-48). crashed=True marks a
    chaos-engine node crash (kubernetriks_tpu/chaos.py): it rides this
    exact removal chain — same interruption/reschedule semantics — and
    carries its pre-sampled repair span for the downtime metric."""

    node_name: str
    crashed: bool = False
    downtime_s: float = 0.0


@dataclass
class RemoveNodeResponse:
    """persistent storage -> api server (reference: src/core/events.rs:52-55)."""

    node_name: str


@dataclass
class NodeRemovedFromCluster:
    """node component -> api server -> persistent storage
    (reference: src/core/events.rs:58-62)."""

    removal_time: float
    node_name: str
    crashed: bool = False
    downtime_s: float = 0.0


@dataclass
class RemoveNodeFromCache:
    """persistent storage -> scheduler (reference: src/core/events.rs:67-70)."""

    node_name: str
    crashed: bool = False  # the scheduler counts crash-caused reschedules


@dataclass
class AddNodeToCache:
    """persistent storage -> scheduler (reference: src/core/events.rs:122-125)."""

    node: Node


# --- pod lifecycle ----------------------------------------------------------


@dataclass
class CreatePodRequest:
    """client/HPA -> api server (reference: src/core/events.rs:75-78)."""

    pod: Pod


@dataclass
class RemovePodRequest:
    """client/HPA -> api server (reference: src/core/events.rs:85-88)."""

    pod_name: str


@dataclass
class RemovePodResponse:
    """persistent storage -> api server (reference: src/core/events.rs:92-96)."""

    assigned_node: Optional[str]
    pod_name: str


@dataclass
class PodRemovedFromNode:
    """node component -> api server -> persistent storage
    (reference: src/core/events.rs:99-106). `removed` is False when the pod had
    already finished before the removal request reached the node."""

    removed: bool
    removal_time: float
    pod_name: str


@dataclass
class RemovePodFromCache:
    """persistent storage -> scheduler (reference: src/core/events.rs:109-112)."""

    pod_name: str


@dataclass
class PodScheduleRequest:
    """persistent storage -> scheduler (reference: src/core/events.rs:115-118)."""

    pod: Pod


@dataclass
class AssignPodToNodeRequest:
    """scheduler -> api server -> persistent storage
    (reference: src/core/events.rs:129-134)."""

    assign_time: float
    pod_name: str
    node_name: str


@dataclass
class AssignPodToNodeResponse:
    """persistent storage -> api server (reference: src/core/events.rs:138-147).
    fail_after: chaos-engine pod-failure draw for THIS attempt (seconds
    after start at which the attempt fails); None = runs to completion."""

    pod_name: str
    pod_requests: RuntimeResources
    pod_group: Optional[str]
    pod_group_creation_time: Optional[str]
    node_name: str
    pod_duration: Optional[float]
    resources_usage_model_config: Optional[RuntimeResourcesUsageModelConfig]
    fail_after: Optional[float] = None


@dataclass
class PodNotScheduled:
    """scheduler -> api server -> persistent storage
    (reference: src/core/events.rs:151-155)."""

    not_scheduled_time: float
    pod_name: str


@dataclass
class BindPodToNodeRequest:
    """api server -> node component (reference: src/core/events.rs:158-167)."""

    pod_name: str
    pod_requests: RuntimeResources
    pod_group: Optional[str]
    pod_group_creation_time: Optional[str]
    node_name: str
    pod_duration: Optional[float]
    resources_usage_model_config: Optional[RuntimeResourcesUsageModelConfig]
    fail_after: Optional[float] = None  # chaos: attempt fails this long after start


@dataclass
class BindPodToNodeResponse:
    """node component -> api server (reference: src/core/events.rs:170-175)."""

    pod_name: str
    pod_duration: Optional[float]
    node_name: str


@dataclass
class PodStartedRunning:
    """node component -> api server -> persistent storage
    (reference: src/core/events.rs:179-183)."""

    pod_name: str
    start_time: float


@dataclass
class PodFinishedRunning:
    """node component (self) -> api server -> persistent storage
    (reference: src/core/events.rs:186-192). finish_result is PodSucceeded or
    PodFailed."""

    pod_name: str
    node_name: str
    finish_time: float
    finish_result: PodConditionType


@dataclass
class RequeuePodAfterBackoff:
    """scheduler -> itself (chaos engine): deliver a CrashLoopBackOff'd pod
    into the active queue at its backoff-expiry time. The active queue is
    drained whole by each cycle (timestamps are priority, not eligibility),
    so a future-timestamped entry must not be pushed early."""

    pod_name: str
    requeue_ts: float


# --- pod groups / HPA -------------------------------------------------------


@dataclass
class CreatePodGroupRequest:
    """client -> api server (reference: src/core/events.rs:196-199). pod_group is
    a benchmark.oracle.autoscalers.interface.PodGroup."""

    pod_group: Any


@dataclass
class RegisterPodGroup:
    """api server -> HPA (reference: src/core/events.rs:203-206). info is a
    benchmark.oracle.autoscalers.interface.PodGroupInfo."""

    info: Any


# --- self-tick cycles -------------------------------------------------------


@dataclass
class RunSchedulingCycle:
    """scheduler -> itself (reference: src/core/events.rs:209-210)."""


@dataclass
class RunClusterAutoscalerCycle:
    """cluster autoscaler -> itself (reference: src/core/events.rs:213-214)."""


@dataclass
class RunHorizontalPodAutoscalerCycle:
    """HPA -> itself (reference: src/core/events.rs:217-218)."""


@dataclass
class RunPodMetricsCollectionCycle:
    """metrics collector -> itself (reference: src/core/events.rs:221-222)."""


@dataclass
class RecordGaugeMetricsCycle:
    """metrics collector -> itself (reference: src/core/events.rs:225-226)."""


@dataclass
class FlushUnschedulableQueueLeftover:
    """scheduler -> itself (reference: src/core/events.rs:246-247)."""


# --- cluster autoscaler info protocol ---------------------------------------


@dataclass
class ClusterAutoscalerRequest:
    """CA -> api server -> persistent storage (reference: src/core/events.rs:230-233).
    request_type is an autoscalers.interface.AutoscaleInfoRequestType."""

    request_type: Any


@dataclass
class ClusterAutoscalerResponse:
    """persistent storage -> api server -> CA (reference: src/core/events.rs:236-240).
    scale_up / scale_down are autoscalers.interface.{ScaleUpInfo, ScaleDownInfo}."""

    scale_up: Optional[Any]
    scale_down: Optional[Any]
