"""kube-api-server component: the central router of the control plane.

Mirrors the reference's KubeApiServer (reference: src/core/api_server.rs):
every request/response passes through it; it owns the node-component pool and
the created-nodes map, tracks pending node-creation/node-removal/pod-removal
requests to resolve same-tick races, and expands pod groups.

Known-deviation note: the reference's RemovePodRequest handler inserts the pod
name into the *node*-removal pending set (api_server.rs:342-343) — an upstream
bug flagged in SURVEY.md §5.2. Here the pod name goes into the pod-removal
pending set, which is what the AssignPodToNodeRequest race check actually
consults.
"""

from __future__ import annotations

from typing import Dict, Optional, Set, TYPE_CHECKING

from benchmark.oracle.core.events import (
    AssignPodToNodeRequest,
    AssignPodToNodeResponse,
    BindPodToNodeRequest,
    ClusterAutoscalerRequest,
    ClusterAutoscalerResponse,
    CreateNodeRequest,
    CreateNodeResponse,
    CreatePodGroupRequest,
    CreatePodRequest,
    NodeAddedToCluster,
    NodeRemovedFromCluster,
    PodFinishedRunning,
    PodNotScheduled,
    PodRemovedFromNode,
    PodStartedRunning,
    RegisterPodGroup,
    RemoveNodeRequest,
    RemoveNodeResponse,
    RemovePodRequest,
    RemovePodResponse,
)
from benchmark.oracle.core.node_component import NodeComponent, NodeComponentPool
from benchmark.oracle.core.types import Node
from benchmark.oracle.sim.kernel import EventHandler, SimulationContext

if TYPE_CHECKING:
    from benchmark.oracle.config import SimulationConfig
    from benchmark.oracle.metrics.collector import MetricsCollector


class KubeApiServer(EventHandler):
    def __init__(
        self,
        persistent_storage_id: int,
        ctx: SimulationContext,
        config: "SimulationConfig",
        metrics_collector: "MetricsCollector",
        cluster_autoscaler_id: Optional[int] = None,
        horizontal_pod_autoscaler_id: Optional[int] = None,
    ) -> None:
        self.persistent_storage = persistent_storage_id
        self.cluster_autoscaler = cluster_autoscaler_id
        self.horizontal_pod_autoscaler = horizontal_pod_autoscaler_id
        self.ctx = ctx
        self.config = config
        self.node_pool: Optional[NodeComponentPool] = None
        self.pending_node_creation_requests: Dict[str, Node] = {}
        self.pending_node_removal_requests: Set[str] = set()
        self.pending_pod_removal_requests: Set[str] = set()
        self.created_nodes: Dict[str, NodeComponent] = {}
        self.metrics_collector = metrics_collector
        # Chaos engine (chaos.py): crash/recovery identity threaded across
        # the storage round-trips (name -> sampled downtime), and the pod
        # fault oracle installed by the simulator when fault injection is on.
        self.crashed_nodes_in_flight: Dict[str, float] = {}
        self.recovered_nodes_pending: Set[str] = set()
        self.fault_oracle = None

    # --- direct API (used by the simulator and tests) -----------------------

    def add_node_component(self, node_component: NodeComponent) -> None:
        node_name = node_component.node_name()
        if node_name in self.created_nodes:
            raise RuntimeError(
                f"Trying to add node {node_name!r} to api server which already exists"
            )
        self.created_nodes[node_name] = node_component

    def all_created_nodes(self):
        return list(self.created_nodes.values())

    def get_node_component(self, node_name: str) -> Optional[NodeComponent]:
        return self.created_nodes.get(node_name)

    def node_count(self) -> int:
        return len(self.created_nodes)

    def set_node_pool(self, node_pool: NodeComponentPool) -> None:
        self.node_pool = node_pool

    def _handle_create_node(self, node_name: str, add_time: float) -> None:
        """Node info is persisted — allocate the simulation component
        (reference: src/core/api_server.rs:96-115)."""
        node = self.pending_node_creation_requests.pop(node_name)
        component = self.node_pool.allocate_component(node, self.ctx.id, self.config)
        self.add_node_component(component)
        recovered = node_name in self.recovered_nodes_pending
        self.recovered_nodes_pending.discard(node_name)
        self.ctx.emit(
            NodeAddedToCluster(
                add_time=add_time, node_name=node_name, recovered=recovered
            ),
            self.persistent_storage,
            self.config.as_to_ps_network_delay,
        )

    def _handle_node_removal(self, node_name: str) -> None:
        component = self.created_nodes.pop(node_name)
        self.node_pool.reclaim_component(component)

    # --- event handlers -----------------------------------------------------

    def on_create_node_request(self, data: CreateNodeRequest, time: float) -> None:
        node = data.node
        node.status.allocatable = node.status.capacity.copy()
        self.metrics_collector.gauge_metrics.current_nodes += 1
        if data.recovered:
            self.recovered_nodes_pending.add(node.metadata.name)
        self.pending_node_creation_requests[node.metadata.name] = node
        self.ctx.emit(
            CreateNodeRequest(node=node.copy()),
            self.persistent_storage,
            self.config.as_to_ps_network_delay,
        )

    def on_create_node_response(self, data: CreateNodeResponse, time: float) -> None:
        self._handle_create_node(data.node_name, time)

    def on_create_pod_request(self, data: CreatePodRequest, time: float) -> None:
        self.metrics_collector.gauge_metrics.current_pods += 1
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_assign_pod_to_node_request(
        self, data: AssignPodToNodeRequest, time: float
    ) -> None:
        """Race checks: the scheduler may assign to a node that is being removed
        or to a pod that is being removed (reference: src/core/api_server.rs:163-193).
        Dropping the request is safe — the scheduler will reschedule/forget on
        the corresponding cache-removal event."""
        if (
            data.node_name in self.pending_node_removal_requests
            or data.node_name not in self.created_nodes
        ):
            return
        if data.pod_name in self.pending_pod_removal_requests:
            return
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_assign_pod_to_node_response(
        self, data: AssignPodToNodeResponse, time: float
    ) -> None:
        node_component = self.created_nodes[data.node_name]
        self.ctx.emit(
            BindPodToNodeRequest(
                pod_name=data.pod_name,
                pod_requests=data.pod_requests,
                pod_group=data.pod_group,
                pod_group_creation_time=data.pod_group_creation_time,
                node_name=data.node_name,
                pod_duration=data.pod_duration,
                resources_usage_model_config=data.resources_usage_model_config,
                fail_after=data.fail_after,
            ),
            node_component.id,
            self.config.as_to_node_network_delay,
        )

    def on_pod_not_scheduled(self, data: PodNotScheduled, time: float) -> None:
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_pod_started_running(self, data: PodStartedRunning, time: float) -> None:
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_pod_finished_running(self, data: PodFinishedRunning, time: float) -> None:
        from benchmark.oracle.core.types import PodConditionType

        metrics = self.metrics_collector
        if data.finish_result == PodConditionType.POD_FAILED:
            # Chaos-engine pod failure (chaos.py): record the restart; a pod
            # within its restart limit re-enters the scheduling queue after
            # backoff (downstream: storage keeps it, the scheduler requeues),
            # one past the limit terminates as permanently failed.
            new_restarts = self.fault_oracle.record_failure(data.pod_name)
            if new_restarts <= self.fault_oracle.restart_limit:
                metrics.accumulated_metrics.pod_restarts += 1
            else:
                metrics.accumulated_metrics.pods_failed += 1
                metrics.accumulated_metrics.internal.terminated_pods += 1
                metrics.gauge_metrics.current_pods -= 1
        else:
            metrics.accumulated_metrics.internal.terminated_pods += 1
            metrics.accumulated_metrics.pods_succeeded += 1
            metrics.gauge_metrics.current_pods -= 1
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_remove_node_request(self, data: RemoveNodeRequest, time: float) -> None:
        self.pending_node_removal_requests.add(data.node_name)
        if data.crashed:
            self.crashed_nodes_in_flight[data.node_name] = data.downtime_s
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_remove_node_response(self, data: RemoveNodeResponse, time: float) -> None:
        node_component = self.created_nodes[data.node_name]
        downtime = self.crashed_nodes_in_flight.pop(data.node_name, None)
        self.ctx.emit(
            RemoveNodeRequest(
                node_name=data.node_name,
                crashed=downtime is not None,
                downtime_s=downtime or 0.0,
            ),
            node_component.id,
            self.config.as_to_node_network_delay,
        )

    def on_node_removed_from_cluster(
        self, data: NodeRemovedFromCluster, time: float
    ) -> None:
        self.metrics_collector.gauge_metrics.current_nodes -= 1
        if data.crashed:
            # Crash accounting lands when the node component actually went
            # down (the batched path folds it at the same effect time).
            am = self.metrics_collector.accumulated_metrics
            am.node_crashes += 1
            am.node_downtime_s += data.downtime_s
        self._handle_node_removal(data.node_name)
        self.pending_node_removal_requests.discard(data.node_name)
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_cluster_autoscaler_request(
        self, data: ClusterAutoscalerRequest, time: float
    ) -> None:
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_cluster_autoscaler_response(
        self, data: ClusterAutoscalerResponse, time: float
    ) -> None:
        self.ctx.emit(data, self.cluster_autoscaler, self.config.as_to_ca_network_delay)

    def on_remove_pod_request(self, data: RemovePodRequest, time: float) -> None:
        self.pending_pod_removal_requests.add(data.pod_name)
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_remove_pod_response(self, data: RemovePodResponse, time: float) -> None:
        if data.assigned_node is not None:
            node_component = self.created_nodes.get(data.assigned_node)
            if node_component is None:
                # The pod's node was removed while this pod-removal was in
                # flight; the node can no longer confirm, so confirm on its
                # behalf: the self-emitted PodRemovedFromNode flows through the
                # normal handler (metrics, pending cleanup) and on to storage,
                # which tells the scheduler to drop the pod — without this the
                # scheduler would reschedule a pod storage already removed.
                # (Deviation: the reference unwraps and panics here.)
                self.ctx.emit_now(
                    PodRemovedFromNode(
                        removed=True, removal_time=time, pod_name=data.pod_name
                    ),
                    self.ctx.id,
                )
                return
            self.ctx.emit(
                RemovePodRequest(pod_name=data.pod_name),
                node_component.id,
                self.config.as_to_node_network_delay,
            )
        else:
            self.pending_pod_removal_requests.discard(data.pod_name)

    def on_pod_removed_from_node(self, data: PodRemovedFromNode, time: float) -> None:
        self.pending_pod_removal_requests.discard(data.pod_name)
        if data.removed:
            metrics = self.metrics_collector
            metrics.accumulated_metrics.internal.terminated_pods += 1
            metrics.accumulated_metrics.pods_removed += 1
            metrics.gauge_metrics.current_pods -= 1
        self.ctx.emit(data, self.persistent_storage, self.config.as_to_ps_network_delay)

    def on_create_pod_group_request(
        self, data: CreatePodGroupRequest, time: float
    ) -> None:
        """Expand the group template into initial_pod_count CreatePodRequests and
        register the group with the HPA (reference: src/core/api_server.rs:405-455)."""
        from benchmark.oracle.autoscalers.interface import PodGroupInfo

        pod_group = data.pod_group
        assert pod_group.pod_template.spec.running_duration is None, (
            "Pod groups with specified duration are not supported. "
            "Only long running services."
        )
        info = PodGroupInfo(creation_time=time, pod_group=pod_group)
        for idx in range(pod_group.initial_pod_count):
            pod = pod_group.pod_template.copy()
            pod_name = f"{pod_group.name}_{idx}"
            pod.metadata.name = pod_name
            pod.metadata.labels["pod_group"] = pod_group.name
            pod.metadata.labels["pod_group_creation_time"] = repr(time)
            pod.spec.resources.usage_model_config = pod_group.resources_usage_model_config
            self.ctx.emit(
                CreatePodRequest(pod=pod),
                self.persistent_storage,
                self.config.as_to_ps_network_delay,
            )
            info.created_pods.add(pod_name)
            info.total_created += 1

        self.metrics_collector.gauge_metrics.current_pods += pod_group.initial_pod_count

        if self.horizontal_pod_autoscaler is not None:
            self.ctx.emit(
                RegisterPodGroup(info=info),
                self.horizontal_pod_autoscaler,
                self.config.as_to_hpa_network_delay,
            )
