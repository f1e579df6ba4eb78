"""Seeded labelled traces for the topology-spread tests: nodes over zones of
unequal size (some without the key), pods of which most carry one of G
workloads' constraint (selector = the workload's own label) and some carry
another workload's label without its constraint. Names are zero-padded so
that the scalar scheduler's sorted-name tie-break equals the batched path's
slot order."""

import numpy as np

from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

GiB = 1024**3
ZONE_KEY = "topology.kubernetes.io/zone"


def spread_traces(
    seed: int,
    n_nodes: int,
    n_pods: int,
    workloads: int,
    zones: int,
    max_skew: int = 1,
    horizon: float = 400.0,
    node_cpu: int = 8000,
    keyless_share: float = 0.1,
    remove_nodes: bool = False,
):
    rng = np.random.default_rng(seed)
    # Unequal zones: zone z draws weight z + 1.
    weights = np.arange(1, zones + 1, dtype=float)
    cluster_events = []
    for i in range(n_nodes):
        labels = {"rack": f"r{i % 4}"}
        if rng.random() >= keyless_share:
            labels[ZONE_KEY] = f"zone-{rng.choice(zones, p=weights / weights.sum())}"
        cluster_events.append(
            {
                "timestamp": 0.0,
                "event_type": {
                    "__tag__": "CreateNode",
                    "node": {
                        "metadata": {"name": f"node_{i:03d}", "labels": labels},
                        "status": {"capacity": {"cpu": node_cpu, "ram": 16 * GiB}},
                    },
                },
            }
        )
        if remove_nodes and rng.random() < 0.15:
            # Mid-cycle: a removal whose news is still on its way to the
            # scheduler at a cycle boundary is the node-side twin of the
            # freed-resource race (docs/PARITY.md), not this plugin's.
            cluster_events.append(
                {
                    "timestamp": float(
                        10.0 * rng.integers(5, int(horizon) // 10) + np.round(rng.uniform(1.0, 9.0), 3)
                    ),
                    "event_type": {"__tag__": "RemoveNode", "node_name": f"node_{i:03d}"},
                }
            )
    workload_events = []
    for i in range(n_pods):
        ts = float(np.round(rng.uniform(1.0, horizon), 3))
        spec = {
            "resources": {
                "requests": {"cpu": 1000, "ram": 2 * GiB},
                "limits": {"cpu": 1000, "ram": 2 * GiB},
            },
            "running_duration": float(np.round(rng.uniform(20.0, 150.0), 3)),
        }
        labels = {}
        draw = rng.random()
        if draw < 0.7:
            w = int(rng.integers(workloads))
            labels["color"] = f"c{w}"
            spec["topology_spread_constraints"] = [
                {
                    "max_skew": max_skew,
                    "topology_key": ZONE_KEY,
                    "when_unsatisfiable": "DoNotSchedule",
                    "label_selector": {"match_labels": {"color": f"c{w}"}},
                }
            ]
        elif draw < 0.85:
            # Matches a workload's selector and carries no constraint.
            labels["color"] = f"c{int(rng.integers(workloads))}"
        workload_events.append(
            {
                "timestamp": ts,
                "event_type": {
                    "__tag__": "CreatePod",
                    "pod": {"metadata": {"name": f"pod_{i:04d}", "labels": labels}, "spec": spec},
                },
            }
        )
    return GenericClusterTrace(events=cluster_events), GenericWorkloadTrace(events=workload_events)
