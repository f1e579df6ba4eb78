"""Cluster-autoscaler proxy: periodic scan cycles driving a pluggable algorithm
(reference: src/autoscalers/cluster_autoscaler/cluster_autoscaler.rs).
"""

from __future__ import annotations

from typing import Dict, TYPE_CHECKING

from benchmark.oracle.autoscalers.interface import (
    AutoscaleInfo,
    CaNodeGroup,
    ClusterAutoscalerAlgorithm,
    ScaleDownNodeAction,
    ScaleUpNodeAction,
)
from benchmark.oracle.autoscalers.kube_cluster_autoscaler import (
    CLUSTER_AUTOSCALER_ORIGIN_LABEL,
    KubeClusterAutoscaler,
)
from benchmark.oracle.core.events import (
    ClusterAutoscalerRequest,
    ClusterAutoscalerResponse,
    CreateNodeRequest,
    RemoveNodeRequest,
    RunClusterAutoscalerCycle,
)
from benchmark.oracle.core.types import Node
from benchmark.oracle.sim.kernel import EventHandler, SimulationContext

if TYPE_CHECKING:
    from benchmark.oracle.config import ClusterAutoscalerConfig, SimulationConfig
    from benchmark.oracle.metrics.collector import MetricsCollector


class ClusterAutoscaler(EventHandler):
    """Every scan_interval: request autoscale info from storage (via api
    server), hand it to the algorithm, emit Create/RemoveNodeRequest actions.
    The next cycle fires immediately if the info round-trip exceeded the scan
    interval (reference: cluster_autoscaler.rs:235-266)."""

    def __init__(
        self,
        api_server: int,
        autoscaling_algorithm: ClusterAutoscalerAlgorithm,
        ctx: SimulationContext,
        config: "SimulationConfig",
        metrics_collector: "MetricsCollector",
    ) -> None:
        ca_config = config.cluster_autoscaler
        assert ca_config.node_groups, "node groups cannot be empty for CA"
        self.node_groups: Dict[str, CaNodeGroup] = {}
        for node_group in ca_config.node_groups:
            template_name = node_group.node_template.metadata.name
            assert template_name, "CA node templates must be named"
            assert template_name not in self.node_groups, (
                "unique node group name should be used"
            )
            node_template = node_group.node_template.copy()
            node_template.status.allocatable = node_template.status.capacity.copy()
            node_template.metadata.labels["origin"] = CLUSTER_AUTOSCALER_ORIGIN_LABEL
            node_template.metadata.labels["node_group"] = template_name
            self.node_groups[template_name] = CaNodeGroup(
                node_template=node_template,
                max_count=node_group.max_count,
                current_count=0,
                total_allocated=0,
            )

        self.api_server = api_server
        self.last_cycle_time = 0.0
        self.autoscaling_algorithm = autoscaling_algorithm
        self.ctx = ctx
        self.config = config
        self.metrics_collector = metrics_collector

    def max_nodes(self) -> int:
        return self.config.cluster_autoscaler.max_node_count

    def start(self) -> None:
        self.ctx.emit_self_now(RunClusterAutoscalerCycle())

    def run_cluster_autoscaler_cycle(self, event_time: float) -> None:
        self.last_cycle_time = event_time
        self.ctx.emit(
            ClusterAutoscalerRequest(
                request_type=self.autoscaling_algorithm.info_request_type()
            ),
            self.api_server,
            self.config.as_to_ca_network_delay,
        )

    def _scale_up_request(self, node: Node) -> None:
        self.ctx.emit(
            CreateNodeRequest(node=node),
            self.api_server,
            self.config.as_to_ca_network_delay,
        )
        self.metrics_collector.accumulated_metrics.total_scaled_up_nodes += 1

    def _scale_down_request(self, node_name: str) -> None:
        self.ctx.emit(
            RemoveNodeRequest(node_name=node_name),
            self.api_server,
            self.config.as_to_ca_network_delay,
        )
        self.metrics_collector.accumulated_metrics.total_scaled_down_nodes += 1

    def take_actions(self, actions) -> None:
        for action in actions:
            if isinstance(action, ScaleUpNodeAction):
                self._scale_up_request(action.node)
            elif isinstance(action, ScaleDownNodeAction):
                self._scale_down_request(action.node_name)

    # --- event handlers -----------------------------------------------------

    def on_run_cluster_autoscaler_cycle(
        self, data: RunClusterAutoscalerCycle, time: float
    ) -> None:
        self.run_cluster_autoscaler_cycle(time)

    def on_cluster_autoscaler_response(
        self, data: ClusterAutoscalerResponse, time: float
    ) -> None:
        actions = self.autoscaling_algorithm.autoscale(
            AutoscaleInfo(scale_up=data.scale_up, scale_down=data.scale_down),
            self.node_groups,
            self.config.cluster_autoscaler.max_node_count,
        )
        self.take_actions(actions)
        delay = self.config.cluster_autoscaler.scan_interval
        if time - self.last_cycle_time > self.config.cluster_autoscaler.scan_interval:
            delay = 0.0
        self.ctx.emit_self(RunClusterAutoscalerCycle(), delay)


def resolve_cluster_autoscaler_impl(
    autoscaler_config: "ClusterAutoscalerConfig",
) -> ClusterAutoscalerAlgorithm:
    """reference: cluster_autoscaler.rs:219-233."""
    if autoscaler_config.autoscaler_type == "kube_cluster_autoscaler":
        return KubeClusterAutoscaler(autoscaler_config.kube_cluster_autoscaler)
    raise ValueError(
        f"Unsupported cluster autoscaler implementation: "
        f"{autoscaler_config.autoscaler_type!r}"
    )
