"""Centralized metric store + self-ticking collector.

Mirrors the reference's MetricsCollector (reference: src/metrics/collector.rs):
counters (AccumulatedMetrics), statistical estimators (min/max/mean/population
variance), gauges, a 60 s pod-utilization pull cycle, and a 5 s gauge recording
cycle. The gauge CSV path is configurable (the reference hardcodes
experiments/gauge_metrics.csv at collector.rs:216); None disables the file while
keeping the cycle (gauges still refresh for the HPA and tests).
"""

from __future__ import annotations

import csv
import math
from dataclasses import dataclass, field
from typing import Dict, Optional, TYPE_CHECKING, Tuple

from benchmark.oracle.core.events import (
    RecordGaugeMetricsCycle,
    RunPodMetricsCollectionCycle,
)
from benchmark.oracle.sim.kernel import EventHandler, SimulationContext

if TYPE_CHECKING:
    from benchmark.oracle.core.api_server import KubeApiServer


class Estimator:
    """Streaming min/max/mean/population-variance (Welford), matching the
    estimator bundle the reference builds from the `average` crate
    (reference: src/metrics/collector.rs:15-74)."""

    def __init__(self) -> None:
        self._count = 0
        self._min = math.inf
        self._max = -math.inf
        self._mean = 0.0
        self._m2 = 0.0

    def add(self, value: float) -> None:
        self._count += 1
        self._min = min(self._min, value)
        self._max = max(self._max, value)
        delta = value - self._mean
        self._mean += delta / self._count
        self._m2 += delta * (value - self._mean)

    def min(self) -> float:
        return self._min

    def max(self) -> float:
        return self._max

    def mean(self) -> float:
        return self._mean if self._count else math.nan

    def population_variance(self) -> float:
        return self._m2 / self._count if self._count else math.nan

    def count(self) -> int:
        return self._count

    def as_dict(self) -> Dict[str, float]:
        return {
            "min": self.min(),
            "max": self.max(),
            "mean": self.mean(),
            "variance": self.population_variance(),
        }

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, Estimator):
            return NotImplemented
        return (
            self.min() == other.min()
            and self.max() == other.max()
            and self.mean() == other.mean()
            and (
                self.population_variance() == other.population_variance()
                or (
                    math.isnan(self.population_variance())
                    and math.isnan(other.population_variance())
                )
            )
        )


@dataclass
class InternalMetrics:
    """reference: src/metrics/collector.rs:77-87."""

    processed_nodes: int = 0
    terminated_pods: int = 0


@dataclass
class AccumulatedMetrics:
    """reference: src/metrics/collector.rs:89-192."""

    total_nodes_in_trace: int = 0
    total_pods_in_trace: int = 0
    pods_succeeded: int = 0
    pods_unschedulable: int = 0
    pods_failed: int = 0
    pods_removed: int = 0
    pod_duration_stats: Estimator = field(default_factory=Estimator)
    pod_scheduling_algorithm_latency_stats: Estimator = field(default_factory=Estimator)
    pod_queue_time_stats: Estimator = field(default_factory=Estimator)
    total_scaled_up_nodes: int = 0
    total_scaled_down_nodes: int = 0
    total_scaled_up_pods: int = 0
    total_scaled_down_pods: int = 0
    # Chaos-engine fault accounting (kubernetriks_tpu/chaos.py). pods_failed
    # above counts PERMANENTLY failed pods (restart limit exceeded);
    # pod_restarts counts CrashLoopBackOff requeues.
    node_crashes: int = 0
    node_recoveries: int = 0
    node_downtime_s: float = 0.0  # sum of sampled repair spans of applied crashes
    pod_interruptions: int = 0  # pods rescheduled because their node crashed
    pod_restarts: int = 0
    internal: InternalMetrics = field(default_factory=InternalMetrics)
    # pod group name -> (cpu estimator, ram estimator)
    pod_utilization_metrics: Dict[str, Tuple[Estimator, Estimator]] = field(
        default_factory=dict
    )

    def increment_pod_duration(self, value: float) -> None:
        self.pod_duration_stats.add(value)

    def increment_pod_scheduling_algorithm_latency(self, value: float) -> None:
        self.pod_scheduling_algorithm_latency_stats.add(value)

    def increment_pod_queue_time(self, value: float) -> None:
        self.pod_queue_time_stats.add(value)


@dataclass
class GaugeMetrics:
    """reference: src/metrics/collector.rs:166-192."""

    current_nodes: int = 0
    current_pods: int = 0
    pods_in_scheduling_queues: int = 0
    node_average_cpu_utilization: float = 0.0
    node_average_ram_utilization: float = 0.0
    cluster_total_cpu_utilization: float = 0.0
    cluster_total_ram_utilization: float = 0.0


GAUGE_CSV_COLUMNS = [
    "timestamp",
    "current_nodes",
    "current_pods",
    "pods_in_scheduling_queues",
    "node_average_cpu_utilization",
    "node_average_ram_utilization",
    "cluster_total_cpu_utilization",
    "cluster_total_ram_utilization",
]


class MetricsCollector(EventHandler):
    """reference: src/metrics/collector.rs:194-431."""

    RECORD_INTERVAL = 5.0
    COLLECTION_INTERVAL = 60.0

    def __init__(self, gauge_csv_path: Optional[str] = None) -> None:
        self.api_server_component: Optional["KubeApiServer"] = None
        self.ctx: Optional[SimulationContext] = None
        self.accumulated_metrics = AccumulatedMetrics()
        self.gauge_metrics = GaugeMetrics()
        self._gauge_file = None
        self._gauge_writer = None
        if gauge_csv_path:
            self._gauge_file = open(gauge_csv_path, "w", newline="")
            self._gauge_writer = csv.writer(self._gauge_file)
            self._gauge_writer.writerow(GAUGE_CSV_COLUMNS)

    def set_api_server_component(self, api_server: "KubeApiServer") -> None:
        self.api_server_component = api_server

    def set_context(self, ctx: SimulationContext) -> None:
        self.ctx = ctx

    def start_gauge_metrics_recording(self) -> None:
        self.ctx.emit_self_now(RecordGaugeMetricsCycle())

    def start_pod_metrics_collection(self) -> None:
        self.ctx.emit_self_now(RunPodMetricsCollectionCycle())

    # --- pod utilization pull (HPA input) ----------------------------------

    def collect_pod_metrics(self, event_time: float) -> None:
        """Pull per-pod-group cpu/ram utilization straight from node components
        (direct reads, not events — reference: src/metrics/collector.rs:263-337)."""
        self.accumulated_metrics.pod_utilization_metrics.clear()
        all_nodes = self.api_server_component.all_created_nodes()

        pod_count_in_pod_groups: Dict[str, int] = {}
        for node in all_nodes:
            for info in node.running_pods.values():
                if info.pod_group is not None:
                    pod_count_in_pod_groups[info.pod_group] = (
                        pod_count_in_pod_groups.get(info.pod_group, 0) + 1
                    )

        for node in all_nodes:
            for info in node.running_pods.values():
                if info.pod_group is None:
                    continue
                total = pod_count_in_pod_groups[info.pod_group]
                cpu_util = (
                    info.cpu_usage_model.current_usage(event_time, total)
                    if info.cpu_usage_model
                    else 0.0
                )
                ram_util = (
                    info.ram_usage_model.current_usage(event_time, total)
                    if info.ram_usage_model
                    else 0.0
                )
                utils = self.accumulated_metrics.pod_utilization_metrics.setdefault(
                    info.pod_group, (Estimator(), Estimator())
                )
                utils[0].add(cpu_util)
                utils[1].add(ram_util)

    def pod_metrics_mean_utilization(self) -> Dict[str, Tuple[float, float]]:
        return {
            group: (cpu.mean(), ram.mean())
            for group, (cpu, ram) in self.accumulated_metrics.pod_utilization_metrics.items()
        }

    # --- gauges -------------------------------------------------------------

    def collect_utilizations(self) -> None:
        """reference: src/metrics/collector.rs:352-390."""
        all_nodes = self.api_server_component.all_created_nodes()
        gauges = self.gauge_metrics
        gauges.node_average_cpu_utilization = 0.0
        gauges.node_average_ram_utilization = 0.0
        cluster_cpu_requests = cluster_ram_requests = 0
        cluster_cpu_capacity = cluster_ram_capacity = 0
        node_count = len(all_nodes)

        for node_component in all_nodes:
            status = node_component.runtime.node.status
            cpu_request = status.capacity.cpu - status.allocatable.cpu
            ram_request = status.capacity.ram - status.allocatable.ram
            gauges.node_average_cpu_utilization += cpu_request / status.capacity.cpu
            gauges.node_average_ram_utilization += ram_request / status.capacity.ram
            cluster_cpu_requests += cpu_request
            cluster_ram_requests += ram_request
            cluster_cpu_capacity += status.capacity.cpu
            cluster_ram_capacity += status.capacity.ram

        # Matches the reference's unguarded divisions: NaN when the cluster is
        # empty is avoided here by explicit guards (deviation: the reference
        # would produce NaN/inf; we clamp to 0.0 for clean CSV output).
        if node_count:
            gauges.node_average_cpu_utilization /= node_count
            gauges.node_average_ram_utilization /= node_count
        else:
            gauges.node_average_cpu_utilization = 0.0
            gauges.node_average_ram_utilization = 0.0
        gauges.cluster_total_cpu_utilization = (
            cluster_cpu_requests / cluster_cpu_capacity if cluster_cpu_capacity else 0.0
        )
        gauges.cluster_total_ram_utilization = (
            cluster_ram_requests / cluster_ram_capacity if cluster_ram_capacity else 0.0
        )

    def record_gauge_metrics(self, current_time: float) -> None:
        self.collect_utilizations()
        if self._gauge_writer is not None:
            gauges = self.gauge_metrics
            self._gauge_writer.writerow(
                [
                    current_time,
                    gauges.current_nodes,
                    gauges.current_pods,
                    gauges.pods_in_scheduling_queues,
                    gauges.node_average_cpu_utilization,
                    gauges.node_average_ram_utilization,
                    gauges.cluster_total_cpu_utilization,
                    gauges.cluster_total_ram_utilization,
                ]
            )

    def close(self) -> None:
        if self._gauge_file is not None:
            self._gauge_file.close()
            self._gauge_file = None
            self._gauge_writer = None

    # --- event handlers -----------------------------------------------------

    def on_run_pod_metrics_collection_cycle(
        self, data: RunPodMetricsCollectionCycle, time: float
    ) -> None:
        self.collect_pod_metrics(time)
        self.ctx.emit_self(RunPodMetricsCollectionCycle(), self.COLLECTION_INTERVAL)

    def on_record_gauge_metrics_cycle(
        self, data: RecordGaugeMetricsCycle, time: float
    ) -> None:
        self.record_gauge_metrics(time)
        self.ctx.emit_self(RecordGaugeMetricsCycle(), self.RECORD_INTERVAL)
