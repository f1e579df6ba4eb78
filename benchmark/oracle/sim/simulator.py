"""Simulation orchestrator: wires all components and runs the event loop
(reference: src/simulator.rs).
"""

from __future__ import annotations

import logging
import time as wall_time
from typing import List, Optional, Tuple

from benchmark.oracle.autoscalers.cluster_autoscaler import (
    ClusterAutoscaler,
    resolve_cluster_autoscaler_impl,
)
from benchmark.oracle.autoscalers.horizontal_pod_autoscaler import (
    HorizontalPodAutoscaler,
    resolve_horizontal_pod_autoscaler_impl,
)
from benchmark.oracle.config import SimulationConfig
from benchmark.oracle.core.api_server import KubeApiServer
from benchmark.oracle.core.events import CreateNodeRequest, CreatePodRequest, RemoveNodeRequest
from benchmark.oracle.core.node_component import (
    NodeComponent,
    NodeComponentPool,
    NodeRuntime,
)
from benchmark.oracle.core.persistent_storage import PersistentStorage
from benchmark.oracle.core.scheduler.interface import PodSchedulingAlgorithm
from benchmark.oracle.core.scheduler.kube_scheduler import (
    KubeScheduler,
    kube_scheduler_config_from_spec,
)
from benchmark.oracle.core.scheduler.scheduler import Scheduler
from benchmark.oracle.core.types import Node, NodeConditionType
from benchmark.oracle.metrics.collector import MetricsCollector
from benchmark.oracle.sim.kernel import Simulation
from benchmark.oracle.trace.interface import Trace, TraceEvents

logger = logging.getLogger("benchmark.oracle")


def max_nodes_in_trace(trace_events: TraceEvents) -> int:
    """Max simultaneously-existing node count; sizes the component pool
    (reference: src/simulator.rs:51-65)."""
    count = max_count = 0
    for _, event in trace_events:
        if isinstance(event, CreateNodeRequest):
            count += 1
        elif isinstance(event, RemoveNodeRequest):
            count -= 1
        max_count = max(count, max_count)
    return max_count


class KubernetriksSimulation:
    """reference: src/simulator.rs:35-402."""

    def __init__(
        self, config: SimulationConfig, gauge_csv_path: Optional[str] = None
    ) -> None:
        self.config = config
        self.sim = Simulation(config.seed)

        api_server_ctx = self.sim.create_context("kube_api_server")
        persistent_storage_ctx = self.sim.create_context("persistent_storage")
        scheduler_ctx = self.sim.create_context("scheduler")

        self.metrics_collector = MetricsCollector(gauge_csv_path=gauge_csv_path)
        self.sim.add_handler("metrics_collector", self.metrics_collector)

        self.cluster_autoscaler: Optional[ClusterAutoscaler] = None
        cluster_autoscaler_id = None
        if config.cluster_autoscaler.enabled:
            ca_ctx = self.sim.create_context("cluster_autoscaler")
            self.cluster_autoscaler = ClusterAutoscaler(
                api_server_ctx.id,
                resolve_cluster_autoscaler_impl(config.cluster_autoscaler),
                ca_ctx,
                config,
                self.metrics_collector,
            )
            cluster_autoscaler_id = self.sim.add_handler(
                "cluster_autoscaler", self.cluster_autoscaler
            )

        self.horizontal_pod_autoscaler: Optional[HorizontalPodAutoscaler] = None
        horizontal_pod_autoscaler_id = None
        if config.horizontal_pod_autoscaler.enabled:
            hpa_ctx = self.sim.create_context("horizontal_pod_autoscaler")
            self.horizontal_pod_autoscaler = HorizontalPodAutoscaler(
                api_server_ctx.id,
                resolve_horizontal_pod_autoscaler_impl(config.horizontal_pod_autoscaler),
                hpa_ctx,
                config,
                self.metrics_collector,
            )
            horizontal_pod_autoscaler_id = self.sim.add_handler(
                "horizontal_pod_autoscaler", self.horizontal_pod_autoscaler
            )

        self.api_server = KubeApiServer(
            persistent_storage_ctx.id,
            api_server_ctx,
            config,
            self.metrics_collector,
            cluster_autoscaler_id=cluster_autoscaler_id,
            horizontal_pod_autoscaler_id=horizontal_pod_autoscaler_id,
        )
        api_server_id = self.sim.add_handler("kube_api_server", self.api_server)

        self.metrics_collector.set_context(self.sim.create_context("metrics_collector"))
        self.metrics_collector.set_api_server_component(self.api_server)
        self.metrics_collector.start_pod_metrics_collection()
        self.metrics_collector.start_gauge_metrics_recording()

        self.scheduler = Scheduler(
            api_server_id,
            # The configured profile (config.scheduler_profile; None = the
            # reference default) — same spec the batched engine compiles
            # into its device pipeline, parsed by the one shared parser.
            KubeScheduler(
                kube_scheduler_config_from_spec(
                    getattr(config, "scheduler_profile", None)
                )
            ),
            scheduler_ctx,
            config,
            self.metrics_collector,
        )
        scheduler_id = self.sim.add_handler("scheduler", self.scheduler)

        self.persistent_storage = PersistentStorage(
            api_server_id,
            scheduler_id,
            persistent_storage_ctx,
            config,
            self.metrics_collector,
        )
        self.sim.add_handler("persistent_storage", self.persistent_storage)

    # --- initialization -----------------------------------------------------

    def initialize(self, cluster_trace: Trace, workload_trace: Trace) -> None:
        """reference: src/simulator.rs:200-275."""
        client = self.sim.create_context("client")
        assert self.sim.time() == 0.0

        cluster_trace_events = cluster_trace.convert_to_simulator_events()
        workload_trace_events = workload_trace.convert_to_simulator_events()

        fault_cfg = self.config.fault_injection
        if fault_cfg is not None and fault_cfg.enabled:
            # The program's chaos engine is not part of this copy: a cell
            # with faults brings its own reference (PERF.md, open questions).
            raise NotImplementedError(
                "the benchmark's oracle copy runs no fault injection"
            )

        trace_max_nodes = max_nodes_in_trace(cluster_trace_events)
        autoscaler_max_nodes = (
            self.cluster_autoscaler.max_nodes() if self.cluster_autoscaler else 0
        )
        max_nodes = trace_max_nodes + autoscaler_max_nodes
        logger.info(
            "Node pool capacity=%d (%d from trace and %d from cluster autoscaler)",
            max_nodes,
            trace_max_nodes,
            autoscaler_max_nodes,
        )
        self.api_server.set_node_pool(NodeComponentPool(max_nodes, self.sim))

        self.initialize_default_cluster()

        api_server_id = self.api_server.ctx.id
        for ts, event in cluster_trace_events:
            if isinstance(event, CreateNodeRequest) and not event.recovered:
                self.metrics_collector.accumulated_metrics.total_nodes_in_trace += 1
            client.emit(event, api_server_id, ts)
        for ts, event in workload_trace_events:
            if isinstance(event, CreatePodRequest):
                self.metrics_collector.accumulated_metrics.total_pods_in_trace += 1
            client.emit(event, api_server_id, ts)

        self.scheduler.start()
        if self.cluster_autoscaler is not None:
            self.cluster_autoscaler.start()
        if self.horizontal_pod_autoscaler is not None:
            self.horizontal_pod_autoscaler.start()

    def add_node(self, node: Node) -> None:
        """Direct (event-bypassing) node install into storage + api server +
        scheduler, used for the default cluster (reference: src/simulator.rs:277-301)."""
        node_name = node.metadata.name
        node_ctx = self.sim.create_context(node_name)
        node.update_condition("True", NodeConditionType.NODE_CREATED, 0.0)
        node.status.allocatable = node.status.capacity.copy()

        self.persistent_storage.add_node(node.copy())
        component = NodeComponent(node_ctx)
        component.runtime = NodeRuntime(
            api_server=self.api_server.ctx.id, node=node.copy(), config=self.config
        )
        self.api_server.add_node_component(component)
        self.scheduler.add_node(node.copy())
        self.sim.add_handler(node_name, component)

    def initialize_default_cluster(self) -> None:
        """Node-group naming rules (reference: src/simulator.rs:303-344):
        single named template -> name verbatim; multi named -> name as prefix
        with a running index; unnamed -> default_node_<idx>."""
        if not self.config.default_cluster:
            return
        total_nodes = 0
        for node_group in self.config.default_cluster:
            node_count_in_group = node_group.node_count or 1
            template_name = node_group.node_template.metadata.name

            if node_count_in_group == 1 and template_name:
                node = node_group.node_template.copy()
                node.metadata.name = template_name
                self.add_node(node)
                # NB: matching the reference, the current_nodes gauge is NOT
                # incremented for this path (simulator.rs:314-320 `continue`s
                # before the gauge update).
                continue
            name_prefix = template_name if template_name else "default_node"

            for _ in range(node_count_in_group):
                node = node_group.node_template.copy()
                node.metadata.name = f"{name_prefix}_{total_nodes}"
                self.add_node(node)
                total_nodes += 1
            self.metrics_collector.gauge_metrics.current_nodes += node_count_in_group

    def set_scheduler_algorithm(self, algorithm: PodSchedulingAlgorithm) -> None:
        self.scheduler.set_scheduler_algorithm(algorithm)

    # --- run loops ----------------------------------------------------------

    def run_with_callbacks(self, callbacks) -> None:
        """reference: src/simulator.rs:355-372."""
        callbacks.on_simulation_start(self)
        t = wall_time.perf_counter()
        while callbacks.on_step(self):
            self.sim.step()
        duration = wall_time.perf_counter() - t
        logger.info(
            "Processed %d events in %.2fs (%.0f events/s)",
            self.sim.event_count(),
            duration,
            self.sim.event_count() / duration if duration else float("inf"),
        )
        logger.info("Finished at %s", self.sim.time())
        callbacks.on_simulation_finish(self)

    def run_until_no_events(self) -> None:
        """NB: matching the reference, this re-arms the scheduler cycles
        (simulator.rs:374-387); use run_with_callbacks after initialize()."""
        self.scheduler.start()
        t = wall_time.perf_counter()
        self.sim.step_until_no_events()
        duration = wall_time.perf_counter() - t
        logger.info(
            "Processed %d events in %.2fs (%.0f events/s)",
            self.sim.event_count(),
            duration,
            self.sim.event_count() / duration if duration else float("inf"),
        )

    def step(self) -> None:
        self.sim.step()

    def step_for_duration(self, duration: float) -> None:
        self.sim.step_for_duration(duration)

    def step_until_time(self, until_time: float) -> None:
        self.sim.step_until_time(until_time)
