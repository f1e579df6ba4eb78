"""Seeded node-pool traffic for the `batch_jobs_pools` driver, beside
benchmark/traffic_gen.py (which no later PR edits and whose nodes are one
machine and whose pods one shape): the configuration's pools of machines with
their labels and taints, every node in a zone, and a conditioned Poisson
stream whose every arrival draws a class (by share), then a request shape
(uniformly from the class's list), then a duration (uniformly from the
class's range), the class giving the pod its nodeSelector, its required node
affinity terms and its tolerations.

numpy, seeded from (`--seed`, "pools", cluster); imports nothing of the
program. Records are neutral data (strings, numbers, lists); `to_events` turns
them into the objects of one side, so neither side sees the other's types, and
the reference keeps the placement terms beside its pods by name
(benchmark/pools_reference.py).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.traffic_gen import GIB, derive_seed

# ("create_node", name, cpu, ram, labels, taints) and
# ("create_pod", name, cpu, ram, duration_s, placement), each after its time.
# taints: [(key, value, effect)]; placement: {"node_selector": {k: v},
# "terms": [[(key, operator, [values])]], "tolerations": [(key, operator,
# value, effect)]}, every key present, empty where the class says nothing.
Record = Tuple


def cluster_records(deployment: Dict) -> List[Record]:
    """The deployment's pools, consecutive in node-name order (names are
    zero-padded, so that is the order they are made in), each node with its
    pool's labels and taints and its zone, the zones round-robin in the same
    order."""
    zones = deployment["zones"]
    values = list(zones["values"])
    out: List[Record] = []
    for pool in deployment["pools"]:
        taints = [(t["key"], t["value"], t["effect"]) for t in pool["taints"]]
        for _ in range(int(pool["nodes"])):
            i = len(out)
            labels = {**pool["labels"], zones["key"]: values[i % len(values)]}
            out.append(
                (0.0, "create_node", f"gen_node_{i:04d}", int(pool["cpu_millicores"]),
                 int(pool["ram_gib"] * GIB), labels, list(taints))
            )
    if len(out) != int(deployment["nodes"]):
        raise ValueError(f"pools_gen: the pools hold {len(out)} nodes, the deployment says {deployment['nodes']}")
    return out


def class_placement(cls: Dict) -> Dict:
    return {
        "node_selector": dict(cls.get("node_selector") or {}),
        "terms": [
            [(key, op, list(values)) for key, op, values in term]
            for term in cls.get("node_affinity_terms") or []
        ],
        "tolerations": [tuple(t) for t in cls.get("tolerations") or []],
    }


def workload_records(traffic: Dict, seed: int, cluster: int) -> List[Record]:
    """One cluster's stream: exactly rate x horizon pods at sorted uniform
    instants (a Poisson process given its count, as traffic_gen's: shapes and
    work do not move with the seed), named in arrival order."""
    if traffic.get("pod_group"):
        raise ValueError("pools_gen: a node-pool mix has no HPA pod group")
    plain, classes = traffic["plain"], traffic["classes"]
    rng = np.random.default_rng(derive_seed(seed, "pools", cluster))
    count = int(round(float(plain["rate_per_second"]) * float(plain["horizon_s"])))
    times = np.sort(rng.random(count) * float(plain["horizon_s"]))
    shares = np.asarray([float(c["share"]) for c in classes])
    which = rng.choice(len(classes), size=count, p=shares / shares.sum())
    shape_draw, duration_draw = rng.random(count), rng.random(count)
    placements = [class_placement(c) for c in classes]
    out = []
    for i in range(count):
        cls = classes[which[i]]
        shapes = cls["requests_cores_gib"]
        cores, gib = shapes[int(shape_draw[i] * len(shapes))]
        lo, hi = cls["duration_s"]
        out.append(
            (float(times[i]), "create_pod", f"pod_{i:05d}", int(round(cores * 1000)), int(gib * GIB),
             float(lo + (hi - lo) * duration_draw[i]), placements[which[i]])
        )
    return out


def class_of(traffic: Dict, placement: Dict) -> str:
    """The name of the class a record's placement came from."""
    for cls in traffic["classes"]:
        if class_placement(cls) == placement:
            return cls["name"]
    raise ValueError(f"pools_gen: no class has the placement {placement!r}")


def placements_by_pod(records: Sequence[Record]) -> Dict[str, Dict]:
    return {rec[2]: rec[6] for rec in records if rec[1] == "create_pod"}


def taints_by_node(records: Sequence[Record]) -> Dict[str, List[Tuple[str, str, str]]]:
    return {rec[2]: rec[6] for rec in records if rec[1] == "create_node"}


def to_events(records: Sequence[Record], api, place=None) -> List[Tuple[float, object]]:
    """Records -> (time, event) pairs of one side. `api` carries that side's
    Node, Pod, CreateNodeRequest and CreatePodRequest; `place(obj, record)`
    puts a node's taints or a pod's placement on that side's object (None:
    the side keeps them beside its objects by name, as the reference does)."""
    out = []
    for rec in records:
        if rec[1] == "create_node":
            t, _, name, cpu, ram, labels, _ = rec
            obj = api.Node.new(name, cpu, ram)
            obj.metadata.labels.update(labels)
            event = api.CreateNodeRequest(node=obj)
        elif rec[1] == "create_pod":
            t, _, name, cpu, ram, duration, _ = rec
            obj = api.Pod.new(name, cpu, ram, duration)
            event = api.CreatePodRequest(pod=obj)
        else:
            raise ValueError(f"unknown node-pool record kind {rec[1]!r}")
        if place is not None:
            place(obj, rec)
        out.append((t, event))
    return out
