"""Default cluster-autoscaler algorithm: bin-pack scale-up, utilization-threshold
scale-down with simulated re-placement
(reference: src/autoscalers/cluster_autoscaler/kube_cluster_autoscaler.rs).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.oracle.autoscalers.interface import (
    CLUSTER_AUTOSCALER_ORIGIN_LABEL,
    AutoscaleInfo,
    AutoscaleInfoRequestType,
    CaNodeGroup,
    ClusterAutoscalerAlgorithm,
    ScaleDownInfo,
    ScaleDownNodeAction,
    ScaleUpInfo,
    ScaleUpNodeAction,
)
from benchmark.oracle.config import KubeClusterAutoscalerConfig
from benchmark.oracle.core.types import Node, Pod


def _node_fits_pod(pod: Pod, node: Node) -> bool:
    requests = pod.spec.resources.requests
    return (
        requests.cpu <= node.status.allocatable.cpu
        and requests.ram <= node.status.allocatable.ram
    )


class KubeClusterAutoscaler(ClusterAutoscalerAlgorithm):
    """Scale-up: first-fit each unscheduled pod into already-planned nodes, then
    a new node from the first fitting group template (respecting per-group
    max_count and the global max_node_count). Scale-down: only CA-origin nodes
    whose max(cpu,ram) utilization is under the threshold and whose pods all fit
    on other nodes (simulated re-placement)."""

    def __init__(self, config: Optional[KubeClusterAutoscalerConfig] = None) -> None:
        self.config = config or KubeClusterAutoscalerConfig()

    def info_request_type(self) -> AutoscaleInfoRequestType:
        return AutoscaleInfoRequestType.AUTO

    # --- scale up -----------------------------------------------------------

    def node_count_over_quota(
        self,
        node_groups: Dict[str, CaNodeGroup],
        current_node_count: int,
        max_node_count: int,
    ) -> bool:
        """reference: kube_cluster_autoscaler.rs:62-80."""
        if current_node_count >= max_node_count:
            return True
        for group in node_groups.values():
            if group.max_count is None or group.current_count < group.max_count:
                return False
        return True

    def try_find_fitting_template(
        self, pod: Pod, node_groups: Dict[str, CaNodeGroup]
    ) -> Optional[Node]:
        """First fitting group in sorted-name order; allocates a uniquely-named
        node from its template (reference: kube_cluster_autoscaler.rs:87-112)."""
        for group_name in sorted(node_groups):
            group = node_groups[group_name]
            if group.max_count is not None and group.current_count >= group.max_count:
                continue
            if _node_fits_pod(pod, group.node_template):
                group.current_count += 1
                group.total_allocated += 1
                node = group.node_template.copy()
                node.metadata.name = f"{node.metadata.name}_{group.total_allocated}"
                node.status.allocatable = node.status.capacity.copy()
                return node
        return None

    @staticmethod
    def _try_fit_in_allocated_nodes(allocated_nodes: List[Node], pod: Pod) -> bool:
        for node in allocated_nodes:
            if _node_fits_pod(pod, node):
                node.status.allocatable.cpu -= pod.spec.resources.requests.cpu
                node.status.allocatable.ram -= pod.spec.resources.requests.ram
                return True
        return False

    def scale_up(
        self,
        info: ScaleUpInfo,
        node_groups: Dict[str, CaNodeGroup],
        max_node_count: int,
    ) -> List[ScaleUpNodeAction]:
        """reference: kube_cluster_autoscaler.rs:190-240."""
        allocated_nodes: List[Node] = []
        current_node_count = sum(g.current_count for g in node_groups.values())
        if self.node_count_over_quota(node_groups, current_node_count, max_node_count):
            return []

        for pod in info.unscheduled_pods:
            if self._try_fit_in_allocated_nodes(allocated_nodes, pod):
                continue
            if current_node_count >= max_node_count:
                continue
            node = self.try_find_fitting_template(pod, node_groups)
            if node is not None:
                # NB: matching the reference, the triggering pod is NOT packed
                # into the fresh node — it joins at full allocatable, and later
                # pods first-fit into it (kube_cluster_autoscaler.rs:210-218).
                allocated_nodes.append(node)
                current_node_count += 1

        actions = []
        for node in allocated_nodes:
            node.status.allocatable = node.status.capacity.copy()
            actions.append(ScaleUpNodeAction(node=node))
        return actions

    # --- scale down ---------------------------------------------------------

    def is_under_threshold_utilization(self, node: Node) -> bool:
        """Utilization = max(cpu, ram) of requests/capacity
        (reference: kube_cluster_autoscaler.rs:117-131)."""
        status = node.status
        cpu_utilization = (status.capacity.cpu - status.allocatable.cpu) / status.capacity.cpu
        ram_utilization = (status.capacity.ram - status.allocatable.ram) / status.capacity.ram
        return max(cpu_utilization, ram_utilization) < (
            self.config.scale_down_utilization_threshold
        )

    @staticmethod
    def all_pods_can_be_moved_to_other_nodes(
        pods: List[Pod], nodes: List[Node], current_node_idx: int
    ) -> bool:
        """Simulated re-placement: greedily place each pod on any other node;
        commits allocatable decrements on success, rolls back on failure
        (reference: kube_cluster_autoscaler.rs:133-181)."""
        if not pods:
            return True
        original = [(n.status.allocatable.cpu, n.status.allocatable.ram) for n in nodes]
        for pod in pods:
            placed = False
            for node_idx, node in enumerate(nodes):
                if node_idx == current_node_idx:
                    continue
                if _node_fits_pod(pod, node):
                    node.status.allocatable.cpu -= pod.spec.resources.requests.cpu
                    node.status.allocatable.ram -= pod.spec.resources.requests.ram
                    placed = True
                    break
            if not placed:
                for node, (cpu, ram) in zip(nodes, original):
                    node.status.allocatable.cpu = cpu
                    node.status.allocatable.ram = ram
                return False
        return True

    def scale_down(
        self, info: ScaleDownInfo, node_groups: Dict[str, CaNodeGroup]
    ) -> List[ScaleDownNodeAction]:
        """reference: kube_cluster_autoscaler.rs:242-290."""
        node_indices_to_remove: List[int] = []
        for idx, node in enumerate(info.nodes):
            if node.metadata.labels.get("origin") != CLUSTER_AUTOSCALER_ORIGIN_LABEL:
                continue
            if not self.is_under_threshold_utilization(node):
                continue
            assigned_pods = info.assignments.get(node.metadata.name)
            if assigned_pods is not None:
                pods_on_node = [
                    info.pods_on_autoscaled_nodes[pod_name]
                    for pod_name in sorted(assigned_pods)
                ]
                if not self.all_pods_can_be_moved_to_other_nodes(
                    pods_on_node, info.nodes, idx
                ):
                    continue
            node_indices_to_remove.append(idx)

        actions = []
        for idx in node_indices_to_remove:
            node = info.nodes[idx]
            node_groups[node.metadata.labels["node_group"]].current_count -= 1
            actions.append(ScaleDownNodeAction(node_name=node.metadata.name))
        return actions

    def autoscale(
        self,
        info: AutoscaleInfo,
        node_groups: Dict[str, CaNodeGroup],
        max_node_count: int,
    ) -> List:
        if info.scale_up is not None:
            return self.scale_up(info.scale_up, node_groups, max_node_count)
        if info.scale_down is not None:
            return self.scale_down(info.scale_down, node_groups)
        return []
