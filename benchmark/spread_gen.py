"""Seeded labelled traffic for the `batch_jobs_labelled` driver, beside
benchmark/traffic_gen.py (which no later PR edits and whose records carry no
labels): the same nodes and the same conditioned Poisson arrivals, plus the
zone label a node and, a pod, the label and the topology-spread constraint of
the workload it belongs to.

Records are neutral data; `to_events` turns them into the objects of one side
(the program's or the oracle copy's), so neither side sees the other's types.
Arrival instants, durations and names are traffic_gen's own
(`workload_records`): a labelled cluster is cell 1's cluster with labels on.
Which workload a pod belongs to comes from a second stream seeded from
(`--seed`, "spread", cluster), so the arrivals do not move with the labels.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import traffic_gen

# ("create_node", name, cpu, ram, labels) and
# ("create_pod", name, cpu, ram, duration_s, labels, constraint | None), each
# after its time; a constraint is (max_skew, topology_key, match_labels).
Record = Tuple
Constraint = Tuple[int, str, Dict[str, str]]


def cluster_records(deployment: Dict) -> List[Record]:
    """traffic_gen's nodes, each labelled with its zone: the configuration's
    values round-robin in node-name order (names are zero-padded, so that is
    the order they are made in)."""
    zones = deployment["zones"]
    values = list(zones["values"])
    nodes = sorted(traffic_gen.cluster_records(deployment), key=lambda rec: rec[2])
    return [
        rec + ({zones["key"]: values[i % len(values)]},) for i, rec in enumerate(nodes)
    ]


def workload_constraint(deployment: Dict, workload: int) -> Tuple[Dict[str, str], Constraint]:
    """(labels, constraint) of a pod of spread workload `workload`: its own
    label value and the source's constraint on it."""
    label = {str(deployment["spread_label_key"]): f"c{workload}"}
    return label, (int(deployment["spread_max_skew"]), str(deployment["zones"]["key"]), dict(label))


def workload_records(deployment: Dict, traffic: Dict, seed: int, cluster: int) -> List[Record]:
    """traffic_gen's stream of the cluster; a pod is unconstrained (and
    unlabelled) with the mix's probability, else uniformly one of the
    configuration's spread workloads."""
    if traffic.get("pod_group"):
        raise ValueError("spread_gen: a labelled mix has no HPA pod group")
    share = float(traffic["spread"]["unconstrained_share"])
    workloads = int(deployment["spread_workloads"])
    rng = random.Random(traffic_gen.derive_seed(seed, "spread", cluster))
    out = []
    for rec in traffic_gen.workload_records(traffic, seed, cluster):
        assert rec[1] == "create_pod", rec
        if rng.random() < share:
            out.append(rec + ({}, None))
        else:
            out.append(rec + workload_constraint(deployment, rng.randrange(workloads)))
    return out


def constraints_by_pod(records: Sequence[Record]) -> Dict[str, Constraint]:
    return {rec[2]: rec[7] for rec in records if rec[1] == "create_pod" and rec[7] is not None}


def to_events(records: Sequence[Record], api, constraint_of=None) -> List[Tuple[float, object]]:
    """Records -> (time, event) pairs of one side. `api` carries that side's
    Node, Pod, CreateNodeRequest and CreatePodRequest; `constraint_of` turns
    a constraint record into that side's object to put on the pod's spec
    (None: the side keeps constraints beside its pods, as the reference
    does: benchmark/spread_reference.py)."""
    out = []
    for rec in records:
        if rec[1] == "create_node":
            t, _, name, cpu, ram, labels = rec
            node = api.Node.new(name, cpu, ram)
            node.metadata.labels.update(labels)
            out.append((t, api.CreateNodeRequest(node=node)))
        elif rec[1] == "create_pod":
            t, _, name, cpu, ram, duration, labels, constraint = rec
            pod = api.Pod.new(name, cpu, ram, duration)
            pod.metadata.labels.update(labels)
            if constraint is not None and constraint_of is not None:
                pod.spec.topology_spread_constraints = [constraint_of(constraint)]
            out.append((t, api.CreatePodRequest(pod=pod)))
        else:
            raise ValueError(f"unknown labelled record kind {rec[1]!r}")
    return out
