"""A configuration file's deployment as the simulator config both sides parse.

benchmark/configs/<name>.json holds the deployment as it is run. This module
renders it as the simulator's YAML; the program and the oracle copy each parse
that text with their own `SimulationConfig.from_yaml`, so neither side's
config object reaches the other.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.traffic_gen import GIB

_DELAY_KEYS = (
    "as_to_ps_network_delay",
    "ps_to_sched_network_delay",
    "sched_to_as_network_delay",
    "as_to_node_network_delay",
    "as_to_ca_network_delay",
    "as_to_hpa_network_delay",
)

# Scenario override -> where the scalar config carries it (bench.py's
# `_scenario_config`); the batched fleet takes the same keys as lane vectors.
_SCENARIO_KEYS = ("hpa_scan_interval", "hpa_tolerance", "ca_scan_interval", "ca_threshold")


def config_yaml(name: str, deployment: Dict, scenario: Optional[Dict] = None) -> str:
    """The deployment (and one what-if scenario's overrides) as config YAML."""
    scenario = dict(scenario or {})
    unknown = set(scenario) - set(_SCENARIO_KEYS)
    if unknown:
        raise ValueError(f"scenario keys the benchmark cannot render: {sorted(unknown)}")
    lines = [
        f"sim_name: benchmark_{name}",
        "seed: 1",
        f"scheduling_cycle_interval: {float(deployment['scheduling_cycle_interval_s'])}",
    ]
    delays = deployment["control_plane_delays_s"]
    for key in _DELAY_KEYS:
        lines.append(f"{key}: {float(delays[key])}")
    if deployment.get("scheduler_profile", "default") != "default":
        lines.append(f"scheduler_profile: {deployment['scheduler_profile']}")
    hpa = deployment.get("horizontal_pod_autoscaler")
    if hpa:
        lines += ["horizontal_pod_autoscaler:", "  enabled: true"]
        if "hpa_scan_interval" in scenario:
            lines.append(f"  scan_interval: {float(scenario['hpa_scan_interval'])}")
        if "hpa_tolerance" in scenario:
            lines += [
                "  kube_horizontal_pod_autoscaler_config:",
                f"    target_threshold_tolerance: {float(scenario['hpa_tolerance'])}",
            ]
    ca = deployment.get("cluster_autoscaler")
    if ca:
        scan = scenario.get("ca_scan_interval", ca["scan_interval_s"])
        lines += [
            "cluster_autoscaler:",
            "  enabled: true",
            f"  scan_interval: {float(scan)}",
            f"  max_node_count: {int(ca['max_node_count'])}",
        ]
        if "ca_threshold" in scenario:
            lines += [
                "  kube_cluster_autoscaler:",
                f"    scale_down_utilization_threshold: {float(scenario['ca_threshold'])}",
            ]
        lines.append("  node_groups:")
        for group in ca["node_groups"]:
            lines += [
                "  - node_template:",
                f"      metadata: {{name: {group['name']}}}",
                "      status: {capacity: {cpu: %d, ram: %d}}"
                % (int(group["cpu_millicores"]), int(group["ram_gib"] * GIB)),
            ]
    return "\n".join(lines) + "\n"
