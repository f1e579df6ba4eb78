"""Default HPA algorithm: k8s desired-replicas formula with tolerance band
(reference: src/autoscalers/horizontal_pod_autoscaler/kube_horizontal_pod_autoscaler.rs).
"""

from __future__ import annotations

import math
from typing import List, Optional

from benchmark.oracle.autoscalers.interface import (
    HorizontalPodAutoscalerAlgorithm,
    PodGroupInfo,
    ScaleDownPodAction,
    ScaleUpPodAction,
)
from benchmark.oracle.config import KubeHorizontalPodAutoscalerConfig


class KubeHorizontalPodAutoscaler(HorizontalPodAutoscalerAlgorithm):
    """desired = ceil(current * currentMetric / targetMetric), skipped when the
    ratio is within the tolerance band around 1.0; per-metric desired values are
    maxed and clamped to the group's max_pod_count."""

    def __init__(
        self, config: Optional[KubeHorizontalPodAutoscalerConfig] = None
    ) -> None:
        self.config = config or KubeHorizontalPodAutoscalerConfig()

    def desired_number_of_pods_by_metric(
        self, current_replicas: int, current_value: float, desired_value: float
    ) -> int:
        """reference: kube_horizontal_pod_autoscaler.rs:54-71."""
        ratio = current_value / desired_value
        if abs(ratio - 1.0) <= self.config.target_threshold_tolerance:
            return current_replicas
        return math.ceil(current_replicas * ratio)

    def desired_number_of_pods(
        self, pod_group: PodGroupInfo, current_cpu: float, current_ram: float
    ) -> int:
        """reference: kube_horizontal_pod_autoscaler.rs:76-155."""
        targets = pod_group.pod_group.target_resources_usage
        current_replicas = len(pod_group.created_pods)
        desired_by_cpu = desired_by_ram = None
        if targets.cpu_utilization is not None:
            desired_by_cpu = self.desired_number_of_pods_by_metric(
                current_replicas, current_cpu, targets.cpu_utilization
            )
        if targets.ram_utilization is not None:
            desired_by_ram = self.desired_number_of_pods_by_metric(
                current_replicas, current_ram, targets.ram_utilization
            )

        max_pods = pod_group.pod_group.max_pod_count
        if desired_by_cpu is not None and desired_by_ram is not None:
            return min(max_pods, max(desired_by_cpu, desired_by_ram))
        if desired_by_cpu is not None:
            return min(max_pods, desired_by_cpu)
        if desired_by_ram is not None:
            return min(max_pods, desired_by_ram)
        return current_replicas

    def make_actions_for_group(
        self, pod_group: PodGroupInfo, desired_number_of_pods: int
    ) -> List:
        """Scale-up clones the template with pod_group labels and a monotonic
        name counter; scale-down pops the lexicographically-first (oldest by
        naming scheme) created pods (reference:
        kube_horizontal_pod_autoscaler.rs:157-216)."""
        actions: List = []
        current_pod_count = len(pod_group.created_pods)
        if current_pod_count == desired_number_of_pods:
            return actions
        if current_pod_count < desired_number_of_pods:
            for _ in range(desired_number_of_pods - current_pod_count):
                new_pod = pod_group.pod_group.pod_template.copy()
                pod_name = f"{pod_group.pod_group.name}_{pod_group.total_created}"
                new_pod.metadata.name = pod_name
                new_pod.metadata.labels["pod_group"] = pod_group.pod_group.name
                new_pod.metadata.labels["pod_group_creation_time"] = repr(
                    pod_group.creation_time
                )
                new_pod.spec.resources.usage_model_config = (
                    pod_group.pod_group.resources_usage_model_config
                )
                actions.append(ScaleUpPodAction(pod=new_pod))
                pod_group.created_pods.add(pod_name)
                pod_group.total_created += 1
        else:
            for _ in range(current_pod_count - desired_number_of_pods):
                next_pod_name = min(pod_group.created_pods)
                pod_group.created_pods.discard(next_pod_name)
                actions.append(ScaleDownPodAction(pod_name=next_pod_name))
        return actions

    def autoscale(self, pod_group_metrics, pod_group_info: PodGroupInfo) -> List:
        desired = self.desired_number_of_pods(
            pod_group_info, pod_group_metrics[0], pod_group_metrics[1]
        )
        return self.make_actions_for_group(pod_group_info, desired)
