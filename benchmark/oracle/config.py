"""Simulation configuration: one YAML file -> SimulationConfig.

Mirrors the reference's config surface (reference: src/config.rs:12-69 and the
autoscaler sub-configs at
src/autoscalers/cluster_autoscaler/cluster_autoscaler.rs:57-96,
src/autoscalers/horizontal_pod_autoscaler/horizontal_pod_autoscaler.rs:39-70,
src/autoscalers/cluster_autoscaler/kube_cluster_autoscaler.rs:34-55,
src/autoscalers/horizontal_pod_autoscaler/kube_horizontal_pod_autoscaler.rs:27-46,
src/metrics/printer.rs:7-18).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml

from benchmark.oracle.core.types import Node


@dataclass
class NodeGroup:
    """Node-group template for the default cluster and the cluster autoscaler.

    Two uses, two count fields (the reference keeps separate types for them):
    - ``node_count`` sizes default-cluster groups (reference: src/config.rs:61-69).
      Naming rules (applied in the simulator): node_count>1 + named template =>
      name used as prefix; node_count None/1 => name used verbatim; unnamed =>
      default_node(_<idx>)? prefix.
    - ``max_count`` caps how many nodes the cluster autoscaler may scale a group
      up to (reference: src/autoscalers/cluster_autoscaler/interface.rs:7-18);
      None means unbounded (up to the global max_node_count).
    """

    node_count: Optional[int] = None
    max_count: Optional[int] = None
    node_template: Node = field(default_factory=Node)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "NodeGroup":
        return NodeGroup(
            node_count=d.get("node_count"),
            max_count=d.get("max_count"),
            node_template=Node.from_dict(d.get("node_template") or {}),
        )


@dataclass
class KubeClusterAutoscalerConfig:
    scale_down_utilization_threshold: float = 0.5

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "KubeClusterAutoscalerConfig":
        if not d:
            return KubeClusterAutoscalerConfig()
        return KubeClusterAutoscalerConfig(
            scale_down_utilization_threshold=float(
                d.get("scale_down_utilization_threshold", 0.5)
            )
        )


@dataclass
class ClusterAutoscalerConfig:
    enabled: bool = False
    autoscaler_type: str = "kube_cluster_autoscaler"
    scan_interval: float = 10.0
    max_node_count: int = 0
    node_groups: List[NodeGroup] = field(default_factory=list)
    kube_cluster_autoscaler: Optional[KubeClusterAutoscalerConfig] = None

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "ClusterAutoscalerConfig":
        if not d:
            return ClusterAutoscalerConfig()
        return ClusterAutoscalerConfig(
            enabled=bool(d.get("enabled", False)),
            autoscaler_type=d.get("autoscaler_type", d.get("type", "kube_cluster_autoscaler")),
            scan_interval=float(d.get("scan_interval", 10.0)),
            max_node_count=int(d.get("max_node_count", 0)),
            node_groups=[NodeGroup.from_dict(g) for g in d.get("node_groups") or []],
            kube_cluster_autoscaler=(
                KubeClusterAutoscalerConfig.from_dict(d["kube_cluster_autoscaler"])
                if d.get("kube_cluster_autoscaler") is not None
                else None
            ),
        )


@dataclass
class KubeHorizontalPodAutoscalerConfig:
    target_threshold_tolerance: float = 0.1

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "KubeHorizontalPodAutoscalerConfig":
        if not d:
            return KubeHorizontalPodAutoscalerConfig()
        return KubeHorizontalPodAutoscalerConfig(
            target_threshold_tolerance=float(d.get("target_threshold_tolerance", 0.1))
        )


@dataclass
class HorizontalPodAutoscalerConfig:
    enabled: bool = False
    autoscaler_type: str = "kube_horizontal_pod_autoscaler"
    scan_interval: float = 60.0
    kube_horizontal_pod_autoscaler_config: Optional[KubeHorizontalPodAutoscalerConfig] = None

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "HorizontalPodAutoscalerConfig":
        if not d:
            return HorizontalPodAutoscalerConfig()
        return HorizontalPodAutoscalerConfig(
            enabled=bool(d.get("enabled", False)),
            autoscaler_type=d.get(
                "autoscaler_type", d.get("type", "kube_horizontal_pod_autoscaler")
            ),
            scan_interval=float(d.get("scan_interval", 60.0)),
            kube_horizontal_pod_autoscaler_config=(
                KubeHorizontalPodAutoscalerConfig.from_dict(
                    d["kube_horizontal_pod_autoscaler_config"]
                )
                if d.get("kube_horizontal_pod_autoscaler_config") is not None
                else None
            ),
        )


_FAULT_DISTRIBUTIONS = ("exponential", "fixed")


def _checked_distribution(value: Any) -> str:
    dist = str(value)
    if dist not in _FAULT_DISTRIBUTIONS:
        raise ValueError(
            f"fault_injection distribution must be one of "
            f"{_FAULT_DISTRIBUTIONS}, got {dist!r}"
        )
    return dist


@dataclass
class NodeFaultConfig:
    """Per-node crash/recovery process. mttf <= 0 disables the channel.
    distribution: "exponential" (default) or "fixed" (deterministic spans).
    Draws are clamped below at one scheduling interval (chaos.py)."""

    mttf: float = 0.0  # mean time to failure, seconds
    mttr: float = 60.0  # mean time to recovery, seconds
    distribution: str = "exponential"

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "NodeFaultConfig":
        if not d:
            return NodeFaultConfig()
        return NodeFaultConfig(
            mttf=float(d.get("mttf", 0.0)),
            mttr=float(d.get("mttr", 60.0)),
            distribution=_checked_distribution(
                d.get("distribution", "exponential")
            ),
        )


@dataclass
class PodFaultConfig:
    """Pod-level failure with CrashLoopBackOff retry. fail_prob <= 0
    disables the channel. A failed attempt re-enters the scheduling queue
    after min(backoff_base * 2^k, backoff_cap) seconds (k = restarts so
    far); a pod whose restart count exceeds restart_limit is marked
    permanently failed."""

    fail_prob: float = 0.0
    backoff_base: float = 10.0
    backoff_cap: float = 300.0
    restart_limit: int = 5

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "PodFaultConfig":
        if not d:
            return PodFaultConfig()
        return PodFaultConfig(
            fail_prob=float(d.get("fail_prob", 0.0)),
            backoff_base=float(d.get("backoff_base", 10.0)),
            backoff_cap=float(d.get("backoff_cap", 300.0)),
            restart_limit=int(d.get("restart_limit", 5)),
        )


@dataclass
class FailureGroupConfig:
    """Correlated blast-radius set: one shared crash process takes every
    member down (and back up) together."""

    members: List[str] = field(default_factory=list)
    mttf: float = 0.0
    mttr: float = 60.0
    distribution: str = "exponential"

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "FailureGroupConfig":
        return FailureGroupConfig(
            members=[str(m) for m in d.get("members") or []],
            mttf=float(d.get("mttf", 0.0)),
            mttr=float(d.get("mttr", 60.0)),
            distribution=_checked_distribution(
                d.get("distribution", "exponential")
            ),
        )


@dataclass
class FaultInjectionConfig:
    """Chaos engine (kubernetriks_tpu/chaos.py): stochastic node
    crash/recovery and pod CrashLoopBackOff, bit-identical across the
    scalar and batched paths via a counter-based PRNG on
    (seed, cluster, object, incarnation)."""

    enabled: bool = False
    seed: Optional[int] = None  # defaults to the simulation seed
    horizon: Optional[float] = None  # defaults to the last trace timestamp
    node: NodeFaultConfig = field(default_factory=NodeFaultConfig)
    pod: PodFaultConfig = field(default_factory=PodFaultConfig)
    failure_groups: List[FailureGroupConfig] = field(default_factory=list)

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "FaultInjectionConfig":
        if not d:
            return FaultInjectionConfig()
        return FaultInjectionConfig(
            enabled=bool(d.get("enabled", False)),
            seed=(int(d["seed"]) if d.get("seed") is not None else None),
            horizon=(
                float(d["horizon"]) if d.get("horizon") is not None else None
            ),
            node=NodeFaultConfig.from_dict(d.get("node")),
            pod=PodFaultConfig.from_dict(d.get("pod")),
            failure_groups=[
                FailureGroupConfig.from_dict(g)
                for g in d.get("failure_groups") or []
            ],
        )


@dataclass
class MetricsPrinterConfig:
    format: str = "JSON"  # "JSON" | "PrettyTable"
    output_file: str = ""

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> Optional["MetricsPrinterConfig"]:
        if not d:
            return None
        fmt = d.get("format", "JSON")
        # The reference's YAML uses serde enum tags (`format: !PrettyTable`);
        # plain strings are the canonical form here. A tag on an empty mapping
        # arrives as {"__tag__": name}; an untagged serde-style map as
        # {"PrettyTable": None}.
        if isinstance(fmt, dict):
            fmt = fmt.get("__tag__") or (next(iter(fmt)) if fmt else "JSON")
        return MetricsPrinterConfig(format=str(fmt), output_file=str(d.get("output_file", "")))


@dataclass
class AlibabaWorkloadTraceV2017Paths:
    batch_instance_trace_path: str = ""
    batch_task_trace_path: str = ""
    machine_events_trace_path: Optional[str] = None

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "AlibabaWorkloadTraceV2017Paths":
        return AlibabaWorkloadTraceV2017Paths(
            batch_instance_trace_path=d.get("batch_instance_trace_path", ""),
            batch_task_trace_path=d.get("batch_task_trace_path", ""),
            machine_events_trace_path=d.get("machine_events_trace_path"),
        )


@dataclass
class GenericTracePaths:
    workload_trace_path: str = ""
    cluster_trace_path: str = ""

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "GenericTracePaths":
        return GenericTracePaths(
            workload_trace_path=d.get("workload_trace_path", ""),
            cluster_trace_path=d.get("cluster_trace_path", ""),
        )


@dataclass
class TraceConfig:
    """Exactly one of the two may be set (asserted at CLI entry, mirroring
    reference: src/main.rs:62-65)."""

    alibaba_cluster_trace_v2017: Optional[AlibabaWorkloadTraceV2017Paths] = None
    generic_trace: Optional[GenericTracePaths] = None

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> Optional["TraceConfig"]:
        if not d:
            return None
        return TraceConfig(
            alibaba_cluster_trace_v2017=(
                AlibabaWorkloadTraceV2017Paths.from_dict(d["alibaba_cluster_trace_v2017"])
                if d.get("alibaba_cluster_trace_v2017")
                else None
            ),
            generic_trace=(
                GenericTracePaths.from_dict(d["generic_trace"])
                if d.get("generic_trace")
                else None
            ),
        )


@dataclass
class SimulationConfig:
    sim_name: str = "kubernetriks-tpu"
    seed: int = 0
    trace_config: Optional[TraceConfig] = None
    logs_filepath: Optional[str] = None
    cluster_autoscaler: ClusterAutoscalerConfig = field(
        default_factory=ClusterAutoscalerConfig
    )
    horizontal_pod_autoscaler: HorizontalPodAutoscalerConfig = field(
        default_factory=HorizontalPodAutoscalerConfig
    )
    fault_injection: FaultInjectionConfig = field(
        default_factory=FaultInjectionConfig
    )
    metrics_printer: Optional[MetricsPrinterConfig] = None
    default_cluster: Optional[List[NodeGroup]] = None
    scheduling_cycle_interval: float = 10.0
    # Scheduler profile spec: a NAMED_PROFILE_SPECS string ("default",
    # "best_fit", "balanced_packing") or an explicit mapping
    # {filters: [...], score: [{name, weight}, ...]}. Parsed by
    # core.scheduler.kube_scheduler.kube_scheduler_config_from_spec — the
    # ONE parser both backends share; the batched engine additionally
    # compiles it into kernel statics (batched/pipeline.py) and raises at
    # construction on a profile it cannot lower. None = reference default
    # (Fit + LeastAllocatedResources).
    scheduler_profile: Optional[Any] = None
    enable_unscheduled_pods_conditional_move: bool = False
    # Simulated control-plane network delays in seconds; as = api server,
    # ps = persistent storage, ca = cluster autoscaler, hpa = horizontal pod
    # autoscaler. All are bidirectional (reference: src/config.rs:28-36).
    as_to_ps_network_delay: float = 0.0
    ps_to_sched_network_delay: float = 0.0
    sched_to_as_network_delay: float = 0.0
    as_to_node_network_delay: float = 0.0
    as_to_ca_network_delay: float = 0.0
    as_to_hpa_network_delay: float = 0.0

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "SimulationConfig":
        default_cluster = d.get("default_cluster")
        return SimulationConfig(
            sim_name=d.get("sim_name", "kubernetriks-tpu"),
            seed=int(d.get("seed", 0)),
            trace_config=TraceConfig.from_dict(d.get("trace_config")),
            logs_filepath=d.get("logs_filepath"),
            cluster_autoscaler=ClusterAutoscalerConfig.from_dict(
                d.get("cluster_autoscaler")
            ),
            horizontal_pod_autoscaler=HorizontalPodAutoscalerConfig.from_dict(
                d.get("horizontal_pod_autoscaler")
            ),
            fault_injection=FaultInjectionConfig.from_dict(
                d.get("fault_injection")
            ),
            metrics_printer=MetricsPrinterConfig.from_dict(d.get("metrics_printer")),
            default_cluster=(
                [NodeGroup.from_dict(g) for g in default_cluster]
                if default_cluster
                else None
            ),
            scheduling_cycle_interval=float(d.get("scheduling_cycle_interval", 10.0)),
            scheduler_profile=d.get("scheduler_profile"),
            enable_unscheduled_pods_conditional_move=bool(
                d.get("enable_unscheduled_pods_conditional_move", False)
            ),
            as_to_ps_network_delay=float(d.get("as_to_ps_network_delay", 0.0)),
            ps_to_sched_network_delay=float(d.get("ps_to_sched_network_delay", 0.0)),
            sched_to_as_network_delay=float(d.get("sched_to_as_network_delay", 0.0)),
            as_to_node_network_delay=float(d.get("as_to_node_network_delay", 0.0)),
            as_to_ca_network_delay=float(d.get("as_to_ca_network_delay", 0.0)),
            as_to_hpa_network_delay=float(d.get("as_to_hpa_network_delay", 0.0)),
        )

    @staticmethod
    def from_yaml(text: str) -> "SimulationConfig":
        return SimulationConfig.from_dict(load_yaml_with_tags(text) or {})

    @staticmethod
    def from_file(path: str) -> "SimulationConfig":
        with open(path) as f:
            return SimulationConfig.from_yaml(f.read())


class _TaggedLoader(yaml.SafeLoader):
    """SafeLoader that flattens serde-style YAML tags.

    The reference's YAML uses serde enum tags like ``event_type: !CreatePod {...}``
    and ``format: !PrettyTable`` (reference: src/data/*.yaml, src/config.yaml:6-8).
    A tag on a mapping becomes {"__tag__": name, **mapping}; a tag on an empty
    scalar becomes the bare tag name string.
    """


def _multi_constructor(loader: _TaggedLoader, tag_suffix: str, node: yaml.Node) -> Any:
    if isinstance(node, yaml.MappingNode):
        value = loader.construct_mapping(node, deep=True)
        value["__tag__"] = tag_suffix
        return value
    if isinstance(node, yaml.SequenceNode):
        return {"__tag__": tag_suffix, "items": loader.construct_sequence(node, deep=True)}
    scalar = loader.construct_scalar(node)
    return tag_suffix if scalar in (None, "") else {"__tag__": tag_suffix, "value": scalar}


_TaggedLoader.add_multi_constructor("!", _multi_constructor)


def load_yaml_with_tags(text: str) -> Any:
    return yaml.load(text, Loader=_TaggedLoader)
