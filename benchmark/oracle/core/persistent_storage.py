"""Persistent storage (etcd stand-in): in-memory source of truth.

Mirrors the reference's PersistentStorage (reference:
src/core/persistent_storage.rs): persists every state change before the api
server acts on it, tracks node->pod assignments, the succeeded-pods archive and
the unscheduled-pods cache (which is exactly what cluster-autoscaler scale-up
consumes), and answers autoscaler info requests.
"""

from __future__ import annotations

from typing import Dict, Set, TYPE_CHECKING

from benchmark.oracle.core.events import (
    AddNodeToCache,
    AssignPodToNodeRequest,
    AssignPodToNodeResponse,
    ClusterAutoscalerRequest,
    ClusterAutoscalerResponse,
    CreateNodeRequest,
    CreateNodeResponse,
    CreatePodRequest,
    NodeAddedToCluster,
    NodeRemovedFromCluster,
    PodFinishedRunning,
    PodNotScheduled,
    PodRemovedFromNode,
    PodScheduleRequest,
    PodStartedRunning,
    RemoveNodeFromCache,
    RemoveNodeRequest,
    RemoveNodeResponse,
    RemovePodFromCache,
    RemovePodRequest,
    RemovePodResponse,
)
from benchmark.oracle.core.resource_usage import default_resource_usage_config
from benchmark.oracle.core.types import (
    Node,
    NodeConditionType,
    ObjectsInfo,
    Pod,
    PodConditionType,
    RuntimeResourcesUsageModelConfig,
)
from benchmark.oracle.sim.kernel import EventHandler, SimulationContext

if TYPE_CHECKING:
    from benchmark.oracle.config import SimulationConfig
    from benchmark.oracle.metrics.collector import MetricsCollector


class PersistentStorage(EventHandler):
    def __init__(
        self,
        api_server_id: int,
        scheduler_id: int,
        ctx: SimulationContext,
        config: "SimulationConfig",
        metrics_collector: "MetricsCollector",
    ) -> None:
        self.api_server = api_server_id
        self.scheduler = scheduler_id
        self.storage_data = ObjectsInfo()
        # node name -> set of pod names assigned to it
        self.assignments: Dict[str, Set[str]] = {}
        self.succeeded_pods: Dict[str, Pod] = {}
        # Chaos engine: permanently-failed archive (restart limit exceeded)
        # and the pod fault oracle installed by the simulator.
        self.failed_pods: Dict[str, Pod] = {}
        self.fault_oracle = None
        self.unscheduled_pods_cache: Set[str] = set()
        self.ctx = ctx
        self.config = config
        self.metrics_collector = metrics_collector

    # --- direct API ---------------------------------------------------------

    def add_node(self, node: Node) -> None:
        name = node.metadata.name
        if name in self.storage_data.nodes:
            raise RuntimeError(
                f"Trying to add node {name!r} to persistent storage which already exists"
            )
        self.storage_data.nodes[name] = node
        self.assignments[name] = set()

    def add_pod(self, pod: Pod) -> None:
        name = pod.metadata.name
        if name in self.storage_data.pods:
            raise RuntimeError(
                f"Trying to add pod {name!r} to persistent storage which already exists"
            )
        self.storage_data.pods[name] = pod

    def get_node(self, node_name: str):
        return self.storage_data.nodes.get(node_name)

    def get_pod(self, pod_name: str):
        return self.storage_data.pods.get(pod_name)

    def node_count(self) -> int:
        return len(self.storage_data.nodes)

    def pod_count(self) -> int:
        return len(self.storage_data.pods)

    def scale_up_info(self):
        """Unscheduled pods snapshot, in sorted-name order
        (reference: src/core/persistent_storage.rs:137-146)."""
        from benchmark.oracle.autoscalers.interface import ScaleUpInfo

        return ScaleUpInfo(
            unscheduled_pods=[
                self.storage_data.pods[name].copy()
                for name in sorted(self.unscheduled_pods_cache)
            ]
        )

    def scale_down_info(self):
        """All nodes + pods on autoscaled nodes + assignments snapshot
        (reference: src/core/persistent_storage.rs:148-168)."""
        from benchmark.oracle.autoscalers.interface import (
            CLUSTER_AUTOSCALER_ORIGIN_LABEL,
            ScaleDownInfo,
        )

        nodes = [node.copy() for node in self.storage_data.sorted_nodes()]
        pods_on_autoscaled_nodes: Dict[str, Pod] = {}
        for node in nodes:
            if node.metadata.labels.get("origin") != CLUSTER_AUTOSCALER_ORIGIN_LABEL:
                continue
            for pod_name in self.assignments[node.metadata.name]:
                pods_on_autoscaled_nodes[pod_name] = self.storage_data.pods[
                    pod_name
                ].copy()
        return ScaleDownInfo(
            nodes=nodes,
            pods_on_autoscaled_nodes=pods_on_autoscaled_nodes,
            assignments={name: set(pods) for name, pods in self.assignments.items()},
        )

    def _clean_up_pod_info(self, pod: Pod) -> None:
        """Release the pod's node resources and drop its assignment; tolerant of
        the node having been removed first (reference:
        src/core/persistent_storage.rs:170-183)."""
        node = self.storage_data.nodes.get(pod.status.assigned_node)
        if node is not None:
            node.status.allocatable.cpu += pod.spec.resources.requests.cpu
            node.status.allocatable.ram += pod.spec.resources.requests.ram
        node_assignments = self.assignments.get(pod.status.assigned_node)
        if node_assignments is not None:
            node_assignments.discard(pod.metadata.name)

    # --- event handlers -----------------------------------------------------

    def on_create_node_request(self, data: CreateNodeRequest, time: float) -> None:
        node_name = data.node.metadata.name
        self.add_node(data.node)
        self.ctx.emit(
            CreateNodeResponse(node_name=node_name),
            self.api_server,
            self.config.as_to_ps_network_delay,
        )

    def on_node_added_to_cluster(self, data: NodeAddedToCluster, time: float) -> None:
        node = self.storage_data.nodes[data.node_name]
        node.update_condition("True", NodeConditionType.NODE_CREATED, data.add_time)
        self.ctx.emit(
            AddNodeToCache(node=node.copy()),
            self.scheduler,
            self.config.ps_to_sched_network_delay,
        )
        self.metrics_collector.accumulated_metrics.internal.processed_nodes += 1
        if data.recovered:
            self.metrics_collector.accumulated_metrics.node_recoveries += 1

    def on_create_pod_request(self, data: CreatePodRequest, time: float) -> None:
        """Creation time is the time the pod lands in storage; pods without a
        usage model get the default constant-at-request model
        (reference: src/core/persistent_storage.rs:225-248)."""
        pod = data.pod
        pod.update_condition("True", PodConditionType.POD_CREATED, time)
        if pod.spec.resources.usage_model_config is None:
            pod.spec.resources.usage_model_config = RuntimeResourcesUsageModelConfig(
                cpu_config=default_resource_usage_config(
                    float(pod.spec.resources.requests.cpu)
                ),
                ram_config=default_resource_usage_config(
                    float(pod.spec.resources.requests.ram)
                ),
            )
        self.add_pod(pod)
        self.ctx.emit(
            PodScheduleRequest(pod=pod.copy()),
            self.scheduler,
            self.config.ps_to_sched_network_delay,
        )

    def on_assign_pod_to_node_request(
        self, data: AssignPodToNodeRequest, time: float
    ) -> None:
        pod = self.storage_data.pods[data.pod_name]
        pod.update_condition("True", PodConditionType.POD_SCHEDULED, data.assign_time)
        pod.status.assigned_node = data.node_name
        self.unscheduled_pods_cache.discard(data.pod_name)

        node = self.storage_data.nodes[data.node_name]
        node.status.allocatable.cpu -= pod.spec.resources.requests.cpu
        node.status.allocatable.ram -= pod.spec.resources.requests.ram
        self.assignments[data.node_name].add(data.pod_name)

        # Chaos engine: the attempt's failure draw happens at assignment
        # commit — the same point the batched path draws on device. The draw
        # is a pure counter-PRNG function of (cluster, slot, restarts), so a
        # later-dropped bind desyncs nothing.
        fail_after = (
            self.fault_oracle.attempt(data.pod_name, pod.spec.running_duration)
            if self.fault_oracle is not None
            else None
        )
        self.ctx.emit(
            AssignPodToNodeResponse(
                pod_name=data.pod_name,
                pod_requests=pod.spec.resources.requests.copy(),
                pod_group=pod.metadata.labels.get("pod_group"),
                pod_group_creation_time=pod.metadata.labels.get(
                    "pod_group_creation_time"
                ),
                node_name=data.node_name,
                pod_duration=pod.spec.running_duration,
                resources_usage_model_config=pod.spec.resources.usage_model_config,
                fail_after=fail_after,
            ),
            self.api_server,
            self.config.as_to_ps_network_delay,
        )

    def on_pod_not_scheduled(self, data: PodNotScheduled, time: float) -> None:
        pod = self.storage_data.pods[data.pod_name]
        pod.update_condition(
            "False", PodConditionType.POD_SCHEDULED, data.not_scheduled_time
        )
        self.unscheduled_pods_cache.add(data.pod_name)

    def on_pod_started_running(self, data: PodStartedRunning, time: float) -> None:
        pod = self.storage_data.pods[data.pod_name]
        pod.update_condition("True", PodConditionType.POD_RUNNING, data.start_time)

    def on_pod_finished_running(self, data: PodFinishedRunning, time: float) -> None:
        """A remove request may have raced ahead and dropped the pod from
        storage; the notification to the scheduler goes out regardless
        (reference: src/core/persistent_storage.rs:316-351).

        Chaos-engine failures (finish_result == POD_FAILED): a pod within
        its restart limit stays IN storage — its node resources/assignment
        are released and the scheduler will requeue it after backoff — while
        a permanently-failed pod archives like a finish, minus the duration
        stats (only successful completions count)."""
        if data.pod_name in self.storage_data.pods:
            pod = self.storage_data.pods[data.pod_name]
            if data.finish_result == PodConditionType.POD_FAILED:
                pod.update_condition("True", data.finish_result, data.finish_time)
                self._clean_up_pod_info(pod)
                if self.fault_oracle.is_permanently_failed(data.pod_name):
                    del self.storage_data.pods[data.pod_name]
                    self.failed_pods[data.pod_name] = pod
                else:
                    pod.status.assigned_node = ""
            else:
                del self.storage_data.pods[data.pod_name]
                pod.update_condition("True", data.finish_result, data.finish_time)
                self._clean_up_pod_info(pod)
                self.metrics_collector.accumulated_metrics.increment_pod_duration(
                    pod.spec.running_duration
                )
                self.succeeded_pods[data.pod_name] = pod
        self.ctx.emit(data, self.scheduler, self.config.ps_to_sched_network_delay)

    def on_remove_node_request(self, data: RemoveNodeRequest, time: float) -> None:
        del self.storage_data.nodes[data.node_name]
        del self.assignments[data.node_name]
        self.ctx.emit(
            RemoveNodeResponse(node_name=data.node_name),
            self.api_server,
            self.config.as_to_ps_network_delay,
        )

    def on_node_removed_from_cluster(
        self, data: NodeRemovedFromCluster, time: float
    ) -> None:
        self.ctx.emit(
            RemoveNodeFromCache(node_name=data.node_name, crashed=data.crashed),
            self.scheduler,
            self.config.ps_to_sched_network_delay,
        )

    def on_cluster_autoscaler_request(
        self, data: ClusterAutoscalerRequest, time: float
    ) -> None:
        """reference: src/core/persistent_storage.rs:381-412. Auto mode: scale
        up when there are unscheduled pods, otherwise offer scale-down info."""
        from benchmark.oracle.autoscalers.interface import AutoscaleInfoRequestType

        response = ClusterAutoscalerResponse(scale_up=None, scale_down=None)
        request_type = data.request_type
        if request_type == AutoscaleInfoRequestType.AUTO:
            if not self.unscheduled_pods_cache:
                response.scale_down = self.scale_down_info()
            else:
                response.scale_up = self.scale_up_info()
        elif request_type == AutoscaleInfoRequestType.SCALE_UP_ONLY:
            response.scale_up = self.scale_up_info()
        elif request_type == AutoscaleInfoRequestType.SCALE_DOWN_ONLY:
            response.scale_down = self.scale_down_info()
        elif request_type == AutoscaleInfoRequestType.BOTH:
            response.scale_up = self.scale_up_info()
            response.scale_down = self.scale_down_info()
        self.ctx.emit(response, self.api_server, self.config.as_to_ps_network_delay)

    def on_remove_pod_request(self, data: RemovePodRequest, time: float) -> None:
        """reference: src/core/persistent_storage.rs:413-462."""
        pod_name = data.pod_name
        if pod_name not in self.storage_data.pods:
            # Already removed or finished running - nothing to do.
            self.ctx.emit(
                RemovePodResponse(assigned_node=None, pod_name=pod_name),
                self.api_server,
                self.config.as_to_ps_network_delay,
            )
            return

        pod = self.storage_data.pods.pop(pod_name)
        pod.update_condition("True", PodConditionType.POD_REMOVED, time)
        # Deviation from the reference (which leaks the name here): a removed
        # unschedulable pod must leave the cache, else the next CA scale-up
        # snapshot dereferences a pod that is gone (reference would panic at
        # persistent_storage.rs:140-143).
        self.unscheduled_pods_cache.discard(pod_name)

        assigned_node_name = pod.status.assigned_node
        assigned_node = None
        if assigned_node_name:
            # Pod is (or was) on a node: release resources, then let the api
            # server terminate it on the node component.
            self._clean_up_pod_info(pod)
            assigned_node = assigned_node_name
        else:
            # Pod is still in scheduling queues - tell the scheduler directly.
            self.ctx.emit(
                RemovePodFromCache(pod_name=pod_name),
                self.scheduler,
                self.config.ps_to_sched_network_delay,
            )
        self.ctx.emit(
            RemovePodResponse(assigned_node=assigned_node, pod_name=pod_name),
            self.api_server,
            self.config.as_to_ps_network_delay,
        )

    def on_pod_removed_from_node(self, data: PodRemovedFromNode, time: float) -> None:
        if not data.removed:
            # Pod finished running earlier than the remove request - nothing to do.
            return
        self.ctx.emit(
            RemovePodFromCache(pod_name=data.pod_name),
            self.scheduler,
            self.config.ps_to_sched_network_delay,
        )
