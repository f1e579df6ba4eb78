"""Seeded node-pool traces for the node-affinity and taint tests: nodes of
several machine shapes in pools (one behind a `dedicated=batch:NoSchedule`
taint), every node in a zone; pods that carry nothing, a zonal required
affinity, a nodeSelector, a toleration, or the toleration AND an affinity on
the tainted pool's label, with requests that do not move in lockstep with the
capacities (so the builds rank by the exact key). Names are zero-padded so that
the scalar scheduler's sorted-name tie-break equals the batched path's slot
order."""

import numpy as np

from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

GiB = 1024**3
ZONE_KEY = "topology.kubernetes.io/zone"
TOLERATION = {"key": "dedicated", "operator": "Equal", "value": "batch", "effect": "NoSchedule"}
TAINT = {"key": "dedicated", "value": "batch", "effect": "NoSchedule"}

# pool -> (share of the nodes, cpu millicores, ram GiB, labels, tainted).
# Capacities with no common measure: where two nodes of unequal allocatable
# score EXACTLY alike as rationals (8 cores / 16 GiB against 12 / 24 under
# round requests), the scalar path's float64 breaks the tie by its rounding
# and the exact key by its truncation, either way (docs/PARITY.md "Node
# affinity and taints", what is not held).
POOLS = (
    ("general", 0.55, 7700, 15, {"pool": "general"}, False),
    ("highmem", 0.2, 7900, 31, {"pool": "highmem"}, False),
    ("compute", 0.15, 12100, 23, {"pool": "compute"}, False),
    ("dedicated", 0.1, 4300, 9, {"pool": "dedicated", "dedicated": "batch"}, True),
)
PLAIN_REQUESTS = ((500, 1), (1000, 4), (2000, 4), (4000, 8))


def required(*terms):
    """A pod spec's `affinity` of required terms, each a list of
    (key, operator, values) expressions."""
    return {
        "node_affinity": {
            "required": {
                "node_selector_terms": [
                    {"match_expressions": [{"key": k, "operator": op, "values": list(v)} for k, op, v in term]}
                    for term in terms
                ]
            }
        }
    }


def node_event(name, cpu, ram_gib, labels, tainted=False, timestamp=0.0):
    node = {
        "metadata": {"name": name, "labels": dict(labels)},
        "status": {"capacity": {"cpu": cpu, "ram": ram_gib * GiB}},
    }
    if tainted:
        node["spec"] = {"taints": [dict(TAINT)]}
    return {"timestamp": timestamp, "event_type": {"__tag__": "CreateNode", "node": node}}


def pod_event(name, timestamp, cpu, ram_gib, duration, **placement):
    spec = {
        "resources": {
            "requests": {"cpu": cpu, "ram": ram_gib * GiB},
            "limits": {"cpu": cpu, "ram": ram_gib * GiB},
        },
        "running_duration": duration,
        **placement,
    }
    return {
        "timestamp": timestamp,
        "event_type": {"__tag__": "CreatePod", "pod": {"metadata": {"name": name}, "spec": spec}},
    }


def pool_nodes(n_nodes: int):
    """(name, cpu, ram GiB, labels, tainted) of each node: pools consecutive
    in name order, zones round-robin, at least one node a pool."""
    counts = [max(1, int(round(share * n_nodes))) for _, share, *_ in POOLS]
    counts[0] += n_nodes - sum(counts)
    out = []
    for (_, _, cpu, ram, labels, tainted), count in zip(POOLS, counts):
        for _ in range(count):
            i = len(out)
            out.append((f"node_{i:03d}", cpu, ram, {**labels, ZONE_KEY: f"zone{i % 3 + 1}"}, tainted))
    return out


def pools_traces(
    seed: int,
    n_nodes: int,
    n_pods: int,
    horizon: float = 400.0,
    dedicated_share: float = 0.15,
    remove_nodes: bool = False,
    two_terms: bool = False,
):
    rng = np.random.default_rng(seed)
    cluster_events = []
    for name, cpu, ram, labels, tainted in pool_nodes(n_nodes):
        cluster_events.append(node_event(name, cpu, ram, labels, tainted))
        if remove_nodes and rng.random() < 0.2:
            # Mid-cycle (the freed-resource race is not these filters'); the
            # node returns under its own name, labels and taints.
            gone = float(10.0 * rng.integers(5, int(horizon) // 20) + np.round(rng.uniform(1.0, 9.0), 3))
            cluster_events.append(
                {"timestamp": gone, "event_type": {"__tag__": "RemoveNode", "node_name": name}}
            )
            cluster_events.append(node_event(name, cpu, ram, labels, tainted, timestamp=gone + 60.0))
    workload_events = []
    for i in range(n_pods):
        ts = float(np.round(rng.uniform(1.0, horizon), 3))
        duration = float(np.round(rng.uniform(20.0, 150.0), 3))
        cpu, ram = PLAIN_REQUESTS[int(rng.integers(len(PLAIN_REQUESTS)))]
        draw = rng.random()
        placement = {}
        if draw < dedicated_share:
            # Sized to fill the tainted pool: two cores of its four a pod.
            cpu, ram, duration = 2000, 4, float(np.round(rng.uniform(100.0, 300.0), 3))
            placement = {"tolerations": [dict(TOLERATION)], "affinity": required([("dedicated", "In", ["batch"])])}
        elif draw < dedicated_share + 0.1:
            placement = {"tolerations": [dict(TOLERATION)]}
        elif draw < dedicated_share + 0.25:
            cpu, ram = 2000, 12
            placement = {"node_selector": {"pool": "highmem"}}
        elif draw < dedicated_share + 0.45:
            terms = [[(ZONE_KEY, "In", ["zone1", "zone2"])]]
            if two_terms:
                terms = [
                    [(ZONE_KEY, "In", ["zone1"]), ("pool", "NotIn", ["compute"]), ("pool", "Exists", [])],
                    [("pool", "In", ["compute"]), ("dedicated", "DoesNotExist", [])],
                ]
            placement = {"affinity": required(*terms)}
        workload_events.append(pod_event(f"pod_{i:04d}", ts, cpu, ram, duration, **placement))
    return GenericClusterTrace(events=cluster_events), GenericWorkloadTrace(events=workload_events)
