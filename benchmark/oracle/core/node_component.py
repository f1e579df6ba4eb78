"""Node component: simulates a node (kubelet) executing pods.

Mirrors the reference's NodeComponent (reference: src/core/node_component.rs):
on bind it precomputes the pod's finish as a delayed self-event, builds cpu/ram
usage models, and tracks allocatable; on node removal it cancels all pending
finish events (the one "advanced" queue op the kernel supports); pod removal
has three outcomes (running / canceled-by-node-removal / already-finished).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Set, TYPE_CHECKING

from benchmark.oracle.core.events import (
    BindPodToNodeRequest,
    NodeRemovedFromCluster,
    PodFinishedRunning,
    PodRemovedFromNode,
    PodStartedRunning,
    RemoveNodeRequest,
    RemovePodRequest,
)
from benchmark.oracle.core.resource_usage import (
    ResourceUsageModel,
    resource_usage_model_from_config,
)
from benchmark.oracle.core.types import (
    Node,
    PodConditionType,
    RuntimeResources,
    RuntimeResourcesUsageModelConfig,
)
from benchmark.oracle.sim.kernel import EventHandler, SimulationContext

if TYPE_CHECKING:
    from benchmark.oracle.config import SimulationConfig


@dataclass
class RunningPodInfo:
    """reference: src/core/node_component.rs:24-31."""

    event_id: Optional[int]
    pod_group: Optional[str]
    pod_requests: RuntimeResources
    cpu_usage_model: Optional[ResourceUsageModel]
    ram_usage_model: Optional[ResourceUsageModel]


@dataclass
class NodeRuntime:
    """Installed when the component is allocated from the pool
    (reference: src/core/node_component.rs:50-54)."""

    api_server: int
    node: Node
    config: "SimulationConfig"


class NodeComponent(EventHandler):
    def __init__(self, ctx: SimulationContext) -> None:
        self.ctx = ctx
        self.runtime: Optional[NodeRuntime] = None
        self.running_pods: Dict[str, RunningPodInfo] = {}
        self.canceled_pods: Set[str] = set()
        self.removed = False
        self.removal_time = 0.0

    @property
    def id(self) -> int:
        return self.ctx.id

    def node_name(self) -> str:
        return self.runtime.node.metadata.name

    def get_node(self) -> Node:
        return self.runtime.node

    def context_name(self) -> str:
        return self.ctx.name

    def allocate_pod_requests(self, requests: RuntimeResources) -> None:
        allocatable = self.runtime.node.status.allocatable
        allocatable.cpu -= requests.cpu
        allocatable.ram -= requests.ram

    def free_pod_requests(self, requests: RuntimeResources) -> None:
        allocatable = self.runtime.node.status.allocatable
        allocatable.cpu += requests.cpu
        allocatable.ram += requests.ram

    def cancel_all_running_pods(self) -> None:
        """Cancel pending PodFinishedRunning self-events, free their resources,
        and mark the pods canceled (reference: src/core/node_component.rs:95-112)."""
        for pod_name, info in self.running_pods.items():
            self.canceled_pods.add(pod_name)
            if info.event_id is not None:
                self.ctx.cancel_event(info.event_id)
            self.free_pod_requests(info.pod_requests)
        self.running_pods.clear()

    def simulate_pod_runtime(
        self,
        event_time: float,
        pod_name: str,
        pod_requests: RuntimeResources,
        pod_group: Optional[str],
        pod_group_creation_time: Optional[str],
        pod_duration: Optional[float],
        usage_config: Optional[RuntimeResourcesUsageModelConfig],
        fail_after: Optional[float] = None,
    ) -> None:
        """reference: src/core/node_component.rs:114-176. A finite-duration pod
        schedules its own finish at +duration (+ as_to_node delay so the event
        leaves for the api server at the right simulated time); long-running
        services (duration None) never self-finish. A chaos-engine failing
        attempt (fail_after set) self-finishes EARLY with POD_FAILED — same
        cancellable self-event, so node removal interrupts it identically."""
        event_id: Optional[int] = None
        if fail_after is not None:
            delay = fail_after + self.runtime.config.as_to_node_network_delay
            event_id = self.ctx.emit_self(
                PodFinishedRunning(
                    pod_name=pod_name,
                    node_name=self.runtime.node.metadata.name,
                    finish_time=event_time + fail_after,
                    finish_result=PodConditionType.POD_FAILED,
                ),
                delay,
            )
        elif pod_duration is not None:
            delay = pod_duration + self.runtime.config.as_to_node_network_delay
            event_id = self.ctx.emit_self(
                PodFinishedRunning(
                    pod_name=pod_name,
                    node_name=self.runtime.node.metadata.name,
                    finish_time=event_time + pod_duration,
                    finish_result=PodConditionType.POD_SUCCEEDED,
                ),
                delay,
            )

        cpu_usage_model = ram_usage_model = None
        if usage_config is not None:
            if usage_config.cpu_config is not None:
                cpu_usage_model = resource_usage_model_from_config(
                    usage_config.cpu_config, pod_group_creation_time
                )
            if usage_config.ram_config is not None:
                ram_usage_model = resource_usage_model_from_config(
                    usage_config.ram_config, pod_group_creation_time
                )

        self.allocate_pod_requests(pod_requests)
        self.running_pods[pod_name] = RunningPodInfo(
            event_id=event_id,
            pod_group=pod_group,
            pod_requests=pod_requests,
            cpu_usage_model=cpu_usage_model,
            ram_usage_model=ram_usage_model,
        )

    # --- event handlers -----------------------------------------------------

    def on_bind_pod_to_node_request(
        self, data: BindPodToNodeRequest, time: float
    ) -> None:
        assert not self.removed, (
            "Pod is assigned on node which is being removed, looks like a bug."
        )
        assert data.node_name == self.node_name(), (
            f"Pod is assigned to node with different node name: pod - "
            f"{data.pod_name!r}, current node - {self.node_name()!r}, assigned "
            f"node - {data.node_name!r}"
        )
        self.simulate_pod_runtime(
            time,
            data.pod_name,
            data.pod_requests,
            data.pod_group,
            data.pod_group_creation_time,
            data.pod_duration,
            data.resources_usage_model_config,
            fail_after=data.fail_after,
        )
        self.ctx.emit(
            PodStartedRunning(pod_name=data.pod_name, start_time=time),
            self.runtime.api_server,
            self.runtime.config.as_to_node_network_delay,
        )

    def on_pod_finished_running(self, data: PodFinishedRunning, time: float) -> None:
        info = self.running_pods.pop(data.pod_name)
        self.free_pod_requests(info.pod_requests)
        self.ctx.emit_now(data, self.runtime.api_server)

    def on_remove_node_request(self, data: RemoveNodeRequest, time: float) -> None:
        assert data.node_name == self.node_name(), (
            f"Trying to remove other node than self: {data.node_name!r} vs "
            f"{self.node_name()!r}"
        )
        self.cancel_all_running_pods()
        self.ctx.emit(
            NodeRemovedFromCluster(
                removal_time=time,
                node_name=data.node_name,
                crashed=data.crashed,
                downtime_s=data.downtime_s,
            ),
            self.runtime.api_server,
            self.runtime.config.as_to_node_network_delay,
        )
        self.removed = True
        self.removal_time = time

    def on_remove_pod_request(self, data: RemovePodRequest, time: float) -> None:
        """Three outcomes (reference: src/core/node_component.rs:286-336):
        still running -> cancel + removed=True; canceled by node removal ->
        removed=True at node removal time; already finished -> removed=False."""
        pod_name = data.pod_name
        delay = self.runtime.config.as_to_node_network_delay
        if pod_name in self.running_pods:
            info = self.running_pods.pop(pod_name)
            self.free_pod_requests(info.pod_requests)
            if info.event_id is not None:
                self.ctx.cancel_event(info.event_id)
            response = PodRemovedFromNode(
                removed=True, removal_time=time, pod_name=pod_name
            )
        elif pod_name in self.canceled_pods:
            response = PodRemovedFromNode(
                removed=True, removal_time=self.removal_time, pod_name=pod_name
            )
        else:
            response = PodRemovedFromNode(
                removed=False, removal_time=0.0, pod_name=pod_name
            )
        self.ctx.emit(response, self.runtime.api_server, delay)


class NodeComponentPool:
    """Pre-registered pool of node components (reference:
    src/core/node_component_pool.rs:24-77). The reference needs this because
    DSLab cannot register handlers from inside handlers; kept here for parity
    of capacity semantics — pool exhaustion is a hard error, and capacity is
    pre-sized from the trace + autoscaler maximum before the run."""

    def __init__(self, node_number: int, sim) -> None:
        self.pool = []
        for i in range(node_number):
            context_name = f"pool_node_context_{i}"
            component = NodeComponent(sim.create_context(context_name))
            sim.add_handler(context_name, component)
            self.pool.append(component)

    def __len__(self) -> int:
        return len(self.pool)

    def allocate_component(
        self, node: Node, api_server: int, config: "SimulationConfig"
    ) -> NodeComponent:
        if not self.pool:
            raise RuntimeError("No nodes to allocate in pool")
        component = self.pool.pop(0)
        component.runtime = NodeRuntime(api_server=api_server, node=node, config=config)
        return component

    def reclaim_component(self, component: NodeComponent) -> None:
        component.runtime = None
        component.removed = False
        component.removal_time = 0.0
        component.canceled_pods.clear()
        component.running_pods.clear()
        self.pool.append(component)
