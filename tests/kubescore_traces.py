"""Seeded traces for the kube-scheduler scoring tests: tests/pools_traces.py's
pools (capacities with no common measure, a pool behind
`dedicated=batch:NoSchedule`) with the soft halves on them: the highmem pool
carries `reserved=highmem:PreferNoSchedule`, some pods prefer the compute pool
(weight 50) and zone1 (weight 1), pods that tolerate the dedicated pool's
taint prefer it (weight 50) and lose the preference when it is full, and the
highmem pods tolerate the soft taint."""

import numpy as np

from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from pools_traces import PLAIN_REQUESTS, TAINT, TOLERATION, ZONE_KEY, pod_event, pool_nodes, required

GiB = 1024**3
SOFT_TAINT = {"key": "reserved", "value": "highmem", "effect": "PreferNoSchedule"}


def preferred(*weighted_terms):
    """A pod spec's `affinity` of preferred terms: (weight, [(key, operator,
    values)...]) each."""
    return {
        "node_affinity": {
            "preferred": [
                {
                    "weight": weight,
                    "preference": {
                        "match_expressions": [{"key": k, "operator": op, "values": list(v)} for k, op, v in term]
                    },
                }
                for weight, term in weighted_terms
            ]
        }
    }


def node_event(name, cpu, ram_gib, labels, taints=(), timestamp=0.0):
    node = {
        "metadata": {"name": name, "labels": dict(labels)},
        "status": {"capacity": {"cpu": cpu, "ram": ram_gib * GiB}},
    }
    if taints:
        node["spec"] = {"taints": [dict(t) for t in taints]}
    return {"timestamp": timestamp, "event_type": {"__tag__": "CreateNode", "node": node}}


def kubescore_nodes(n_nodes: int, round_shapes: bool = False):
    """(name, cpu, ram GiB, labels, taints): the pools of pool_nodes, the
    highmem pool softly tainted. `round_shapes`: the benchmark cell's four
    machine shapes (integer scores need no capacities without a common
    measure: equal rationals are equal integers on every path)."""
    shapes = {"general": (64000, 128), "highmem": (64000, 256), "compute": (96000, 192), "dedicated": (32000, 64)}
    out = []
    for name, cpu, ram, labels, tainted in pool_nodes(n_nodes):
        if round_shapes:
            cpu, ram = shapes[labels["pool"]]
        taints = [TAINT] if tainted else [SOFT_TAINT] if labels["pool"] == "highmem" else []
        out.append((name, cpu, ram, labels, taints))
    return out


def kubescore_traces(seed: int, n_nodes: int, n_pods: int, horizon: float = 400.0, round_shapes: bool = False):
    rng = np.random.default_rng(seed)
    cluster_events = [node_event(*node) for node in kubescore_nodes(n_nodes, round_shapes)]
    scale = 8 if round_shapes else 1
    workload_events = []
    for i in range(n_pods):
        ts = float(np.round(rng.uniform(1.0, horizon), 3))
        duration = float(np.round(rng.uniform(20.0, 150.0), 3))
        cpu, ram = PLAIN_REQUESTS[int(rng.integers(len(PLAIN_REQUESTS)))]
        draw = rng.random()
        placement = {}
        if draw < 0.12:
            # Sized to fill the tainted pool: two cores of its four a pod.
            cpu, ram, duration = 2000, 4, float(np.round(rng.uniform(100.0, 300.0), 3))
            placement = {"tolerations": [dict(TOLERATION)], "affinity": required([("dedicated", "In", ["batch"])])}
        elif draw < 0.27:
            # Tolerates the pool and PREFERS it: honoured while it has room.
            placement = {
                "tolerations": [dict(TOLERATION)],
                "affinity": preferred((50, [("dedicated", "In", ["batch"])])),
            }
        elif draw < 0.42:
            cpu, ram = 2000, 12
            placement = {
                "node_selector": {"pool": "highmem"},
                "tolerations": [{"key": "reserved", "operator": "Exists"}],
            }
        elif draw < 0.57:
            placement = {"affinity": required([(ZONE_KEY, "In", ["zone1", "zone2"])])}
        elif draw < 0.8:
            placement = {
                "affinity": preferred(
                    (50, [("pool", "In", ["compute"])]), (1, [(ZONE_KEY, "In", ["zone1"])])
                )
            }
        workload_events.append(pod_event(f"pod_{i:04d}", ts, cpu * scale, ram * scale, duration, **placement))
    return GenericClusterTrace(events=cluster_events), GenericWorkloadTrace(events=workload_events)
