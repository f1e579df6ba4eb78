"""Horizontal-pod-autoscaler proxy
(reference: src/autoscalers/horizontal_pod_autoscaler/horizontal_pod_autoscaler.rs).

Every scan_interval it pulls per-pod-group mean cpu/ram utilization straight
from the MetricsCollector (a direct read, not an event — reference:
horizontal_pod_autoscaler.rs:146-150), runs the algorithm per group, and emits
CreatePodRequest / RemovePodRequest actions.
"""

from __future__ import annotations

from typing import Dict, TYPE_CHECKING

from benchmark.oracle.autoscalers.interface import (
    HorizontalPodAutoscalerAlgorithm,
    PodGroupInfo,
    ScaleDownPodAction,
    ScaleUpPodAction,
)
from benchmark.oracle.autoscalers.kube_horizontal_pod_autoscaler import (
    KubeHorizontalPodAutoscaler,
)
from benchmark.oracle.core.events import (
    CreatePodRequest,
    RegisterPodGroup,
    RemovePodRequest,
    RunHorizontalPodAutoscalerCycle,
)
from benchmark.oracle.core.types import Pod
from benchmark.oracle.sim.kernel import EventHandler, SimulationContext

if TYPE_CHECKING:
    from benchmark.oracle.config import HorizontalPodAutoscalerConfig, SimulationConfig
    from benchmark.oracle.metrics.collector import MetricsCollector


class HorizontalPodAutoscaler(EventHandler):
    def __init__(
        self,
        api_server: int,
        autoscaling_algorithm: HorizontalPodAutoscalerAlgorithm,
        ctx: SimulationContext,
        config: "SimulationConfig",
        metrics_collector: "MetricsCollector",
    ) -> None:
        self.api_server = api_server
        self.pod_groups: Dict[str, PodGroupInfo] = {}
        self.autoscaling_algorithm = autoscaling_algorithm
        self.ctx = ctx
        self.config = config
        self.metrics_collector = metrics_collector

    def start(self) -> None:
        self.ctx.emit_self_now(RunHorizontalPodAutoscalerCycle())

    def _scale_up_request(self, pod: Pod) -> None:
        # NB: the reference emits HPA scale requests with the *CA* delay
        # (horizontal_pod_autoscaler.rs:100-105 uses as_to_ca_network_delay);
        # replicated for golden-trajectory parity.
        self.ctx.emit(
            CreatePodRequest(pod=pod),
            self.api_server,
            self.config.as_to_ca_network_delay,
        )
        self.metrics_collector.accumulated_metrics.total_scaled_up_pods += 1

    def _scale_down_request(self, pod_name: str) -> None:
        self.ctx.emit(
            RemovePodRequest(pod_name=pod_name),
            self.api_server,
            self.config.as_to_ca_network_delay,
        )
        self.metrics_collector.accumulated_metrics.total_scaled_down_pods += 1

    def take_actions(self, actions) -> None:
        for action in actions:
            if isinstance(action, ScaleUpPodAction):
                self._scale_up_request(action.pod)
            elif isinstance(action, ScaleDownPodAction):
                self._scale_down_request(action.pod_name)

    def run_horizontal_pod_autoscaler_cycle(self) -> None:
        """Sorted group order replaces the reference's nondeterministic HashMap
        iteration (horizontal_pod_autoscaler.rs:152-160) — a determinism fix,
        not a semantic change."""
        metrics = self.metrics_collector.pod_metrics_mean_utilization()
        actions = []
        for group_name in sorted(metrics):
            cpu_mean, ram_mean = metrics[group_name]
            pod_group_info = self.pod_groups[group_name]
            actions.extend(
                self.autoscaling_algorithm.autoscale(
                    (cpu_mean, ram_mean), pod_group_info
                )
            )
        self.take_actions(actions)
        self.ctx.emit_self(
            RunHorizontalPodAutoscalerCycle(),
            self.config.horizontal_pod_autoscaler.scan_interval,
        )

    # --- event handlers -----------------------------------------------------

    def on_run_horizontal_pod_autoscaler_cycle(
        self, data: RunHorizontalPodAutoscalerCycle, time: float
    ) -> None:
        self.run_horizontal_pod_autoscaler_cycle()

    def on_register_pod_group(self, data: RegisterPodGroup, time: float) -> None:
        self.pod_groups[data.info.pod_group.name] = data.info


def resolve_horizontal_pod_autoscaler_impl(
    autoscaler_config: "HorizontalPodAutoscalerConfig",
) -> HorizontalPodAutoscalerAlgorithm:
    """reference: horizontal_pod_autoscaler.rs:171-185."""
    if autoscaler_config.autoscaler_type == "kube_horizontal_pod_autoscaler":
        return KubeHorizontalPodAutoscaler(
            autoscaler_config.kube_horizontal_pod_autoscaler_config
        )
    raise ValueError(
        f"Unsupported horizontal pod autoscaler implementation: "
        f"{autoscaler_config.autoscaler_type!r}"
    )
